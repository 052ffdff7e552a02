import pytest

from repro.configs import ASSIGNED, SHAPES, get_config, get_shape

EXPECTED = {
    "xlstm-350m": dict(n_layers=24, d_model=1024, n_heads=4, d_ff=0,
                       vocab=50304),
    "whisper-small": dict(n_layers=12, d_model=768, n_heads=12, d_ff=3072,
                          vocab=51865),
    # as published: tied head, the embedding counted once
    "qwen3-4b": dict(n_layers=36, d_model=2560, n_heads=32, n_kv_heads=8,
                     d_ff=9728, vocab=151936, tie_embeddings=True,
                     param_count=4_022_468_096),
    "kimi-k2-1t-a32b": dict(n_layers=61, d_model=7168, n_heads=64,
                            n_kv_heads=8, vocab=163840),
    "phi3.5-moe-42b-a6.6b": dict(n_layers=32, d_model=4096, n_heads=32,
                                 n_kv_heads=8, vocab=32064),
    "qwen2-7b": dict(n_layers=28, d_model=3584, n_heads=28, n_kv_heads=4,
                     d_ff=18944, vocab=152064),
    "chatglm3-6b": dict(n_layers=28, d_model=4096, n_heads=32, n_kv_heads=2,
                        d_ff=13696, vocab=65024),
    "jamba-1.5-large-398b": dict(n_layers=72, d_model=8192, n_heads=64,
                                 n_kv_heads=8, vocab=65536),
    "gemma2-27b": dict(n_layers=46, d_model=4608, n_heads=32, n_kv_heads=16,
                       d_ff=36864, vocab=256000),
    "pixtral-12b": dict(n_layers=40, d_model=5120, n_heads=32, n_kv_heads=8,
                        d_ff=14336, vocab=131072),
}


def test_all_assigned_present():
    assert set(EXPECTED) == set(ASSIGNED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_exact_config_values(name):
    from repro.models import param_count
    cfg = get_config(name)
    for k, v in EXPECTED[name].items():
        got = param_count(cfg) if k == "param_count" else getattr(cfg, k)
        assert got == v, (name, k, got, v)


def test_moe_configs():
    assert get_config("kimi-k2-1t-a32b").moe.n_experts == 384
    assert get_config("kimi-k2-1t-a32b").moe.top_k == 8
    assert get_config("phi3.5-moe-42b-a6.6b").moe.n_experts == 16
    assert get_config("jamba-1.5-large-398b").moe.top_k == 2


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reduced_limits(name):
    r = get_config(name).reduced()
    assert r.n_layers <= 2 * r.period and r.d_model <= 512
    if r.moe is not None:
        assert r.moe.n_experts <= 4
    assert r.n_layers % r.period == 0


def test_param_counts_match_scale():
    """Sanity: configured sizes land near their nameplate parameter counts."""
    from repro.models import active_param_count, param_count
    assert 0.9e12 < param_count(get_config("kimi-k2-1t-a32b")) < 1.15e12
    assert 25e9 < active_param_count(get_config("kimi-k2-1t-a32b")) < 40e9
    assert 330e9 < param_count(get_config("jamba-1.5-large-398b")) < 430e9
    assert 6e9 < param_count(get_config("qwen2-7b")) < 9e9
    assert 24e9 < param_count(get_config("gemma2-27b")) < 30e9
    assert 0.25e9 < param_count(get_config("xlstm-350m")) < 0.5e9


def test_shapes_registry():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].kind == "decode"
    assert get_shape("long_500k").seq_len == 524_288


def test_long_context_gate():
    assert get_config("xlstm-350m").supports_long_context
    assert get_config("jamba-1.5-large-398b").supports_long_context
    assert get_config("gemma2-27b").supports_long_context
    for n in ("qwen3-4b", "qwen2-7b", "chatglm3-6b", "pixtral-12b",
              "whisper-small", "kimi-k2-1t-a32b", "phi3.5-moe-42b-a6.6b"):
        assert not get_config(n).supports_long_context, n


def test_fl_config_zo_backend_names_the_one_route():
    """"auto" and "ref" both name the one ZO route; the removed flat
    route is refused by name."""
    from repro.configs.base import FLConfig
    assert FLConfig().zo_backend == "auto"
    assert FLConfig(zo_backend="ref").zo_backend == "ref"
    with pytest.raises(ValueError, match="pallas"):
        FLConfig(zo_backend="pallas")
