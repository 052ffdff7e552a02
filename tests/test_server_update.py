"""The server's aggregate-and-update program (``FederatedZO._update``)
against the op-by-op path it replaces: ``VP.aggregate`` of the
concatenated deltas, FedAvgM momentum, then ``space.add``, each eager.
Bits must match for every space, and the program must be traced once and
compile nothing after round 0."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core import virtual_path as VP
from repro.core.server import Client, FederatedZO
from repro.core.spaces import DenseSpace, LoRASpace, MaskedSpace
from repro.fault.plan import RoundFaults


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def _params():
    """bf16 and f32 leaves: a stacked 3-D leaf, 2-D leaves, a 1-D bias, a
    leaf the mask leaves without coordinates, and two LoRA adapters."""
    k = jax.random.split(jax.random.key(0), 6)
    n = jax.random.normal
    return {"stack": {"wo": n(k[0], (3, 8, 16)).astype(jnp.bfloat16),
                      "w1": n(k[1], (3, 16, 8))},
            "bias": n(k[2], (16,)).astype(jnp.bfloat16),
            "emb": n(k[3], (32, 8)),
            "lora_a": n(k[4], (8, 4)),
            "lora_b": n(k[5], (4, 8)).astype(jnp.bfloat16)}


def _masked(params):
    """Distinct indices per leaf in no particular order; none in ``emb``."""
    rng = np.random.default_rng(3)
    counts = {"wo": 40, "w1": 12, "bias": 5, "emb": 0, "lora_a": 3,
              "lora_b": 2}

    def pick(path, leaf):
        name = jax.tree_util.keystr(path).split("'")[-2]
        return jnp.asarray(rng.choice(leaf.size, counts[name], replace=False),
                           jnp.int32)
    return MaskedSpace(jax.tree_util.tree_map_with_path(pick, params))


SPACES = {"masked": _masked, "dense": DenseSpace, "lora": LoRASpace}


def _loss(params, batch):
    s = sum(jnp.mean(l.astype(jnp.float32) ** 2)
            for l in jax.tree.leaves(params))
    return s * jnp.mean(batch["x"] ** 2)


def _server(space, momentum, n_clients=3):
    fl = FLConfig(n_clients=n_clients, local_steps=2, batch_size=2,
                  lr=0.1, eps=1e-3, server_momentum=momentum)
    rng = np.random.default_rng(1)
    clients = [Client(i, {"x": rng.normal(size=(8, 4)).astype(np.float32)},
                      2) for i in range(n_clients)]
    return FederatedZO(_loss, _params(), space, fl, clients)


def _eager_update(srv, params, deltas, n_report, velocity):
    """The op-by-op path, as the round ran before its program existed."""
    if n_report:
        agg = VP.aggregate(
            jnp.concatenate([jnp.asarray(d) for d in deltas], axis=0),
            n_report)
    else:
        agg = jnp.zeros((srv.space.n,), jnp.float32)
    if srv.fl.server_momentum > 0.0:
        velocity = (agg if velocity is None
                    else srv.fl.server_momentum * velocity + agg)
        agg = velocity
    return srv.space.add(params, agg), velocity


def _bits(tree):
    return [np.asarray(x).view(np.uint8).tobytes()
            for x in jax.tree.leaves(tree)]


def _live(space, tree):
    """The leaves the space holds coordinates in, which the program
    returns."""
    return [l for l, m in zip(jax.tree.leaves(tree), space.sizes) if m]


@pytest.mark.parametrize("kind,momentum", [
    ("masked", 0.0), ("masked", 0.5), ("dense", 0.0), ("lora", 0.0),
    ("lora", 0.5)])
def test_update_program_matches_eager_bit_for_bit(kind, momentum):
    params = _params()
    srv = _server(SPACES[kind](params), momentum)
    n = srv.space.n
    rng = np.random.default_rng(7)
    # the program alone, on stacks of 3, 1 and 0 reporters (two groups'
    # replays concatenated, one straggler, a zero-survivor round)
    p_eager, v_prog, v_eager = srv.params, None, None
    for groups in ([2, 1], [1], []):
        deltas = [rng.normal(size=(k, n)).astype(np.float32) * 0.05
                  for k in groups]
        k = sum(groups)
        stack = (np.concatenate(deltas) if deltas
                 else np.zeros((0, n), np.float32))
        live, v_prog = srv._update(p_eager, srv._idx, stack, np.float32(k),
                                   v_prog)
        p_eager, v_eager = _eager_update(srv, p_eager, deltas, k, v_eager)
        assert _bits(live) == _bits(_live(srv.space, p_eager))
        assert (v_prog is None) == (v_eager is None) == (momentum == 0.0)
        if momentum:
            assert _bits(v_prog) == _bits(v_eager)
    assert len(live) == sum(1 for m in srv.space.sizes if m) > 0
    for a, b in zip(live, _live(srv.space, srv.params)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert _bits(a) != _bits(b)
    # three rounds, the third with every client dropped, against a server
    # whose update runs op by op
    prog, eager = _server(SPACES[kind](params), momentum), \
        _server(SPACES[kind](params), momentum)

    def update(p, i, s, k, v):
        p, v = _eager_update(eager, p, [s] if s.shape[0] else [], int(k), v)
        return _live(eager.space, p), v
    eager._update = update
    drop_all = RoundFaults(drops=frozenset(range(3)))
    for f in (None, None, drop_all):
        prog.run_round(faults=f)
        eager.run_round(faults=f)
        assert _bits(prog.params) == _bits(eager.params)
    assert prog.last_round_info["n_reporting"] == 0
    if momentum:
        assert _bits(prog.velocity) == _bits(eager.velocity)


def test_update_program_traced_once_and_compiles_only_in_round_0():
    srv = _server(_masked(_params()), 0.0)
    for _ in range(3):
        srv.run_round()
    assert obs.totals()["counters"]["fl.update_programs"] == 1
    mine = [c for c in obs.export()["compiles"]
            if c["span"] in ("fl.update", "fl.aggregate")]
    # (the jitted jnp helpers traced inside it report traces of their own)
    assert [(c["program"], c["kind"]) for c in mine
            if c["kind"] != "trace"] == [("jit(_aggregate_update)", "lower"),
                                         ("jit(_aggregate_update)", "compile")]
    assert {c["round"] for c in mine} == {0}
