"""The program's spans, counters and compile records (``repro/obs.py``),
and where the program opens them: the federated round's ``fl.*`` spans,
mask calibration's ``mask.*`` spans and ``mask.coords.*`` counters, and
the ZO loop's ``zo.*`` and the forward's ``fwd.*`` scopes, which change
the compiled program's metadata and none of its numbers."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.configs.tiny import TINY
from repro.core import random_mask, sensitivity_mask
from repro.core.server import Client, FederatedZO
from repro.data.synthetic import TaskSpec, make_task_fns, sample_dataset

SPEC = TaskSpec(vocab=min(TINY.vocab, 512))
SCOPES = ("zo.sample", "zo.perturb", "zo.forward", "zo.update")


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def prob():
    from repro.models import Model
    model = Model(TINY)
    params = model.init(jax.random.key(0))
    loss, _, _ = make_task_fns(model, SPEC)
    space = random_mask(params, density=1e-2, seed=0, balanced=False)
    return dict(model=model, params=params, loss=loss, space=space)


def mk_server(prob, backend="ref", n_clients=2, T=2):
    fl = FLConfig(n_clients=n_clients, local_steps=T, batch_size=2,
                  zo_backend=backend)
    clients = [Client(i, sample_dataset(SPEC, 8, seed=i), 2)
               for i in range(n_clients)]
    return FederatedZO(prob["loss"], prob["params"], prob["space"], fl,
                       clients)


def flat(tree):
    return np.concatenate([np.asarray(x, np.float32).ravel()
                           for x in jax.tree.leaves(tree)])


def test_spans_nest_with_parent_and_round():
    with obs.span("outer", round=3):
        with obs.span("mid"):
            with obs.span("inner") as s:
                pass
    with obs.span("alone"):
        pass
    spans = {r["name"]: r for r in obs.export()["spans"]}
    assert [r["name"] for r in obs.export()["spans"]] == [
        "inner", "mid", "outer", "alone"]
    assert (spans["inner"]["parent"], spans["inner"]["round"]) == ("mid", 3)
    assert (spans["mid"]["parent"], spans["mid"]["round"]) == ("outer", 3)
    assert (spans["outer"]["parent"], spans["outer"]["round"]) == (None, 3)
    assert (spans["alone"]["parent"], spans["alone"]["round"]) == (None,
                                                                   None)
    o, i = spans["outer"], spans["inner"]
    assert o["t0_ns"] <= i["t0_ns"] <= i["t1_ns"] <= o["t1_ns"]
    assert s.seconds == (i["t1_ns"] - i["t0_ns"]) * 1e-9


def test_span_closes_on_error_and_keeps_attrs():
    with pytest.raises(ValueError):
        with obs.span("failing", n=1) as s:
            s.attrs["m"] = 2
            raise ValueError
    with obs.span("after"):
        pass
    rec = obs.export()["spans"]
    assert rec[0]["name"] == "failing" and (rec[0]["n"], rec[0]["m"]) == (1,
                                                                          2)
    assert rec[1]["parent"] is None


def test_span_buffer_is_bounded():
    for i in range(obs.MAX_RECORDS + 10):
        with obs.span("s", round=i):
            pass
    spans = obs.export()["spans"]
    assert len(spans) == obs.MAX_RECORDS
    assert spans[-1]["round"] == obs.MAX_RECORDS + 9
    assert spans[0]["round"] == 10


def test_counters_and_totals():
    obs.count("a")
    obs.count("a", 4)
    with obs.span("x"):
        pass
    t = obs.totals()
    assert t["counters"] == {"a": 5}
    assert set(t["spans_s"]) == {"x"} and t["spans_s"]["x"] >= 0
    assert t["span_attrs"] == {}


def test_fresh_jit_is_recorded_once_under_its_span():
    def uniquely_named_program(x):
        return jnp.sin(x) * 3.0 + 1.0

    f = jax.jit(uniquely_named_program)
    x = jnp.ones((5,))
    n0 = obs.compile_count()
    with obs.span("phase", round=7):
        f(x).block_until_ready()
    mine = [c for c in obs.export()["compiles"]
            if c["program"] == "jit(uniquely_named_program)"]
    assert [c["kind"] for c in mine] == ["trace", "lower", "compile"]
    assert all(c["span"] == "phase" and c["round"] == 7 for c in mine)
    assert all(c["seconds"] > 0 for c in mine)
    assert obs.compile_count() == n0 + 1
    n_records = len(obs.export()["compiles"])
    with obs.span("phase"):
        f(x).block_until_ready()
    assert len(obs.export()["compiles"]) == n_records
    assert obs.compile_count() == n0 + 1
    assert obs.totals()["compiles_s"]["jit(uniquely_named_program)"] == \
        pytest.approx(sum(c["seconds"] for c in mine))


ROUND_SPANS = ["fl.inputs", "fl.inputs", "fl.group", "fl.group_wait",
               "fl.uplink", "fl.replay", "fl.uplink", "fl.aggregate",
               "fl.update", "fl.round"]


def test_round_records_fl_spans_in_order_with_comm_bytes(prob):
    srv = mk_server(prob)
    for r in range(2):
        up, down = srv.comm.up_bytes, srv.comm.down_bytes
        srv.run_round()
        spans = [s for s in obs.export()["spans"] if s["round"] == r]
        assert [s["name"] for s in spans] == ROUND_SPANS   # by closing
        root = spans[-1]
        assert root["parent"] is None
        assert all(s["parent"] == "fl.round" for s in spans[:-1])
        assert root["up_bytes"] == srv.comm.up_bytes - up > 0
        assert root["down_bytes"] == srv.comm.down_bytes - down > 0
        opened = sorted(spans[:-1], key=lambda s: s["t0_ns"])
        assert [s["name"] for s in opened] == ROUND_SPANS[:-1]
    assert obs.totals()["span_attrs"] == {"fl.round": {
        "up_bytes": srv.comm.up_bytes, "down_bytes": srv.comm.down_bytes}}
    # one group program built, for the first round only, and traced once
    # on the ref route's in-place perturb, which off the TPU leaves every
    # coordinate to point scatters
    assert obs.export()["counters"] == {
        "fl.programs_built": 1, "zo.perturb_inplace": 1,
        "zo.perturb.block_coords": 0,
        "zo.perturb.point_coords": prob["space"].n}
    first = [c for c in obs.export()["compiles"]
             if c["program"] == "jit(group)" and c["kind"] == "compile"]
    assert len(first) == 1 and first[0]["span"] == "fl.group"
    assert first[0]["round"] == 0


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_perturb_coords_counters_on_cpu(prob, backend):
    """Off the TPU the in-place perturb places no coordinate with the block
    kernel: ``zo.perturb.block_coords`` is 0 and ``point_coords`` the whole
    space, once per group program traced; the flat route counts neither."""
    srv = mk_server(prob, backend=backend)
    srv.run_round()
    srv.run_round()
    c = obs.totals()["counters"]
    if backend == "pallas":
        assert not any(k.startswith("zo.perturb") for k in c)
        return
    assert (c["zo.perturb.block_coords"], c["zo.perturb.point_coords"]) == (
        0, prob["space"].n)


def test_vp_calibration_span(prob):
    srv = mk_server(prob)
    gp = jnp.full((prob["space"].n,), 0.01, jnp.float32)
    srv.calibrate_vp(gp, T_cali=3)
    srv.run_round(gp_vec=gp)
    names = [s["name"] for s in obs.export()["spans"]]
    assert names[0] == "fl.vp_calibration"
    assert names.count("fl.gradip") == 2   # one per client in the round


def test_mask_calibration_spans(prob):
    pre = [{"tokens": np.asarray(sample_dataset(SPEC, 4, seed=9)["tokens"])}]
    model = prob["model"]
    sensitivity_mask(lambda p, b: model.loss(p, b), prob["params"], pre,
                     1e-2)
    names = [s["name"] for s in obs.export()["spans"]]
    assert names == ["mask.scores", "mask.to_host", "mask.topk",
                     "mask.to_device"]


def test_mask_coords_counters_sum_to_the_mask(prob):
    """One ``mask.coords.<leaf>`` counter per leaf, each that leaf's
    chosen coordinates, summing to round(N * density)."""
    pre = [{"tokens": np.asarray(sample_dataset(SPEC, 4, seed=9)["tokens"])}]
    model = prob["model"]
    space = sensitivity_mask(lambda p, b: model.loss(p, b), prob["params"],
                             pre, 1e-2)
    coords = {k[len("mask.coords."):]: v
              for k, v in obs.export()["counters"].items()
              if k.startswith("mask.coords.")}
    paths = jax.tree_util.tree_flatten_with_path(space.idx_tree)[0]
    assert coords == {jax.tree_util.keystr(path, simple=True, separator="."):
                      int(ix.shape[0]) for path, ix in paths}
    assert coords["stack.p0.wq"] == len(space.idx_tree["stack"]["p0"]["wq"])
    n = sum(x.size for x in jax.tree.leaves(prob["params"]))
    assert sum(coords.values()) == space.n == round(n * 1e-2)


def _scopes_in(hlo: str, prefix: str = "zo.") -> set:
    names = re.findall(r'op_name="([^"]*)"', hlo)
    return {p for n in names for p in n.split("/") if p.startswith(prefix)}


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen3-4b"])
def test_group_program_carries_forward_scopes(arch):
    """The forward's ``fwd.*`` scopes reach the group program's
    ``op_name`` metadata, nested in ``zo.forward``; ``fwd.qk_norm`` only
    where the model norms q and k."""
    from repro.configs import get_config
    from repro.models import Model
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    loss, _, _ = make_task_fns(model, SPEC)
    space = random_mask(params, density=1e-2, seed=0, balanced=False)
    srv = mk_server(dict(loss=loss, params=params, space=space))
    srv.run_round()
    hlo = srv.group_hlo_text(2, 2)
    want = {"fwd.attn", "fwd.mlp", "fwd.head"}
    assert _scopes_in(hlo, "fwd.") == (want | {"fwd.qk_norm"}
                                       if cfg.qk_norm else want)
    # (an op that a custom-JVP function such as the softmax lowers keeps
    # only the scopes opened inside its enclosing layer loop)
    nested = {p for n in re.findall(r'op_name="([^"]*)"', hlo)
              if "zo.forward" in n.split("/")
              for p in n.split("/") if p.startswith("fwd.")}
    assert nested == _scopes_in(hlo, "fwd.")


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_group_hlo_text_carries_every_zo_scope(prob, backend):
    """The text is the executable the round ran, not a second compile."""
    srv = mk_server(prob, backend=backend)
    srv.run_round()
    assert srv.zo_routes[(2, 2)] == backend
    n = obs.compile_count()
    hlo = srv.group_hlo_text(2, 2)
    assert obs.compile_count() == n   # the round's own executable
    assert "ENTRY" in hlo
    assert _scopes_in(hlo) == set(SCOPES)


def _strip_metadata(hlo: str) -> str:
    """The computations, without the source tables before them and
    without each instruction's metadata, each name replaced by the order
    of its first appearance (a scope may change the numbers XLA gives
    the names, ``%broadcast_in_dim.895`` for ``.891``, and nothing else)."""
    body = hlo[re.search(r"\n(%|ENTRY)", hlo).start():]
    body = re.sub(r", metadata=\{[^}]*\}", "", body)
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  body)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_scopes_leave_round_bit_identical(prob, backend, monkeypatch):
    """The scoped round and the same round traced with every
    ``jax.named_scope`` a no-op return the same scalars and parameters,
    bit for bit, from programs that differ only in metadata."""
    scoped = mk_server(prob, backend=backend)
    gs_a = [scoped.run_round() for _ in range(2)]
    hlo_a = scoped.group_hlo_text(2, 2)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = mk_server(prob, backend=backend)
    gs_b = [plain.run_round() for _ in range(2)]
    hlo_b = plain.group_hlo_text(2, 2)
    assert not _scopes_in(hlo_b)
    for a, b in zip(gs_a, gs_b):
        assert a.keys() == b.keys()
        for cid in a:
            np.testing.assert_array_equal(a[cid], b[cid])
    np.testing.assert_array_equal(flat(scoped.params), flat(plain.params))
    assert _strip_metadata(hlo_a) == _strip_metadata(hlo_b)
