"""Fleet-scale federated rounds (DESIGN.md §12): seeded client sampling,
the quantized ZO uplink, and their composition with faults, checkpoints
and the sharded round — the invariants the K-in-the-thousands protocol
rests on:

* sampler determinism + bit-exact RNG state resume,
* ``sample_frac=1.0`` + identity codec == today's dense round bitwise
  (unsharded and under a 1x1 FLShardPlan),
* exact-replay quantization: the virtual path reconstructs bit-exactly
  from the encoded wire payload alone,
* CommLog bills encoded wire bytes for exactly the cohort,
* server state stays O(seeds + scalars) in K.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import CheckpointError
from repro.checkpoint.state import server_state_sizes
from repro.configs.base import FLConfig
from repro.configs.tiny import TINY
from repro.core import random_mask
from repro.core import virtual_path as VP
from repro.core.fl_step import make_fl_train_loop
from repro.core.gradip import gradip_matrix
from repro.core.quantize import IdentityCodec, IntCodec
from repro.core.sampling import ClientSampler
from repro.core.seeds import round_keys
from repro.core.server import Client, FederatedZO
from repro.data.synthetic import TaskSpec, make_task_fns, sample_dataset
from repro.fault import FaultPlan, RoundFaults

SPEC = TaskSpec(vocab=min(TINY.vocab, 512))


@pytest.fixture(scope="module")
def prob():
    from repro.models import Model
    model = Model(TINY)
    params = model.init(jax.random.key(0))
    loss, per_example, _ = make_task_fns(model, SPEC)
    space = random_mask(params, density=1e-2, seed=0, balanced=False)
    gp = jnp.full((space.n,), 0.01, jnp.float32)
    return dict(params=params, loss=loss, per_example=per_example,
                space=space, gp=gp)


def mk_server(prob, n_clients=6, T=2, frac=1.0, quantize="none",
              weighted=False, plan=None, sampler=None, codec=None):
    fl = FLConfig(n_clients=n_clients, local_steps=T, batch_size=2,
                  sample_frac=frac, quantize=quantize,
                  sample_weighted=weighted)
    clients = [Client(i, sample_dataset(SPEC, 8, seed=i), 2)
               for i in range(n_clients)]
    return FederatedZO(prob["loss"], prob["params"], prob["space"], fl,
                       clients, plan=plan, sampler=sampler, codec=codec)


def flat(tree):
    return np.concatenate([np.asarray(x, np.float32).ravel()
                           for x in jax.tree.leaves(tree)])


def assert_servers_equal(a, b):
    assert np.array_equal(flat(a.params), flat(b.params))
    assert (a.comm.up_bytes, a.comm.down_bytes) == \
        (b.comm.up_bytes, b.comm.down_bytes)
    assert a.round == b.round
    assert [c.ptr for c in a.clients] == [c.ptr for c in b.clients]
    assert a.early_stopped == b.early_stopped
    for cid in a.gradip_log:
        ea, eb = a.gradip_log[cid], b.gradip_log[cid]
        assert len(ea) == len(eb)
        for u, v in zip(ea, eb):
            assert (u is None) == (v is None)
            if u is not None:
                assert np.array_equal(u, v)


# -- ClientSampler -----------------------------------------------------------

def test_sampler_deterministic_and_well_formed():
    a = ClientSampler(range(20), frac=0.25, seed=3)
    b = ClientSampler(range(20), frac=0.25, seed=3)
    seen = set()
    for r in range(30):
        ca, cb = a.cohort(r), b.cohort(r)
        assert ca == cb  # same seed => same draws, round by round
        assert len(ca) == 5 == len(set(ca))
        assert ca == tuple(sorted(ca))
        assert set(ca) <= set(range(20))
        seen |= set(ca)
    assert seen == set(range(20))  # uniform draws cover the fleet
    assert ClientSampler(range(20), frac=0.25, seed=4).cohort() != \
        a.__class__(range(20), frac=0.25, seed=3).cohort()


def test_sampler_lockstep_enforced():
    s = ClientSampler(range(8), frac=0.5, seed=0)
    s.cohort(0)
    with pytest.raises(ValueError, match="out-of-order"):
        s.cohort(0)  # re-draw of a consumed round
    with pytest.raises(ValueError, match="out-of-order"):
        s.cohort(5)  # skipping ahead
    s.cohort(1)  # in-order continues fine
    s.cohort()   # rnd=None skips the check (manual driving)


def test_sampler_cohort_size_and_validation():
    assert ClientSampler(range(10), frac=1.0).m == 10
    assert ClientSampler(range(10), frac=0.04).m == 1  # floor at 1
    assert ClientSampler(range(10), m=3).m == 3
    with pytest.raises(ValueError, match="frac"):
        ClientSampler(range(4), frac=0.0)
    with pytest.raises(ValueError, match="cohort size"):
        ClientSampler(range(4), m=5)
    with pytest.raises(ValueError, match="duplicate"):
        ClientSampler([1, 1, 2], m=1)
    with pytest.raises(ValueError, match="need frac or m"):
        ClientSampler(range(4))


def test_sampler_weighted_draws():
    w = [0.0] * 6 + [1.0] * 6
    s = ClientSampler(range(12), m=3, weights=w, seed=1)
    assert s.weighted
    for r in range(20):
        assert set(s.cohort(r)) <= set(range(6, 12))  # zero weight => never
    with pytest.raises(ValueError, match="positive"):
        ClientSampler(range(4), m=3, weights=[1, 0, 0, 0])
    with pytest.raises(ValueError, match="shape"):
        ClientSampler(range(4), m=2, weights=[1, 1])


def test_sampler_state_roundtrip_bitexact():
    """Restoring a mid-stream state_dict re-draws the identical cohort
    sequence — the sampled analogue of the seed-ladder resume."""
    ref = ClientSampler(range(32), frac=0.25, seed=9)
    ref_draws = [ref.cohort(r) for r in range(10)]
    src = ClientSampler(range(32), frac=0.25, seed=9)
    for r in range(4):
        src.cohort(r)
    snap = src.state_dict()
    fresh = ClientSampler(range(32), frac=0.25, seed=9)
    fresh.load_state(snap)
    assert [fresh.cohort(r) for r in range(4, 10)] == ref_draws[4:]
    other = ClientSampler(range(16), frac=0.5, seed=9)
    with pytest.raises(ValueError, match="mismatch"):
        other.load_state(snap)


# -- sampled rounds ----------------------------------------------------------

def test_sampled_round_semantics(prob):
    """Only the cohort runs: bytes, data pointers, and GradIP entries for
    everyone else stay untouched, with explicit None gaps in the log."""
    srv = mk_server(prob, n_clients=6, frac=0.5)
    assert srv.sampler is not None and srv.sampler.m == 3
    T = srv.fl.local_steps
    for r in range(4):
        before = {c.cid: c.ptr for c in srv.clients}
        up0, down0 = srv.comm.up_bytes, srv.comm.down_bytes
        gs = srv.run_round(gp_vec=prob["gp"])
        cohort = srv.last_round_info["cohort"]
        assert sorted(gs) == cohort and len(cohort) == 3
        assert srv.last_round_info["n_unsampled"] == 3
        # traffic: exactly m encoded uploads + m downlinks
        assert srv.comm.up_bytes - up0 == \
            sum(srv.codec.nbytes(np.asarray(gs[c]).size) for c in cohort)
        assert srv.comm.down_bytes - down0 == 3 * srv._down_bytes(T)
        for c in srv.clients:
            gap = srv.gradip_log[c.cid][-1]
            if c.cid in cohort:
                # ptr advances (mod the client's data size)
                assert gap is not None and c.ptr != before[c.cid]
            else:
                assert gap is None and c.ptr == before[c.cid]
    # the log renders as a gap-aware matrix aligned with participation
    mat, present = gradip_matrix(srv.gradip_log[0], T=T)
    assert mat.shape == (4, T)
    for r in range(4):
        assert present[r] == (not np.isnan(mat[r]).all())


def test_unsampled_round_gradip_gap_alignment(prob):
    """gradip_matrix's present mask reproduces each client's sampled
    rounds exactly."""
    srv = mk_server(prob, n_clients=6, frac=0.5)
    cohorts = []
    for r in range(5):
        srv.run_round(gp_vec=prob["gp"])
        cohorts.append(set(srv.last_round_info["cohort"]))
    for c in srv.clients:
        _, present = gradip_matrix(srv.gradip_log[c.cid],
                                   T=srv.fl.local_steps)
        assert list(present) == [c.cid in coh for coh in cohorts]


def test_weighted_sampling_prefers_data_rich_clients(prob):
    srv = mk_server(prob, n_clients=6, frac=0.5, weighted=True)
    assert srv.sampler.weighted


def test_faults_restrict_to_cohort(prob):
    """A fault schedule drawn over the full fleet composes with any
    participation fraction: events outside the cohort are no-ops."""
    rf = RoundFaults(drops=frozenset({0, 1, 2, 3}), late={4: 1, 5: 2})
    r = rf.restrict({1, 4})
    assert r.drops == {1} and r.late == {4: 1} and not r.kill
    assert RoundFaults().restrict({0}).empty
    kill = RoundFaults(kill=True).restrict(set())
    assert kill.kill  # server-side preemption ignores the cohort

    # through the server: a drop aimed at an unsampled client changes
    # nothing vs the fault-free sampled round
    clean = mk_server(prob, frac=0.5)
    clean.run_round(gp_vec=prob["gp"])
    outside = [c.cid for c in clean.clients
               if c.cid not in clean.last_round_info["cohort"]]
    faulty = mk_server(prob, frac=0.5)
    faulty.run_round(gp_vec=prob["gp"],
                     faults=RoundFaults(drops=frozenset(outside)))
    assert_servers_equal(clean, faulty)
    assert faulty.last_round_info["drops"] == []


def test_sampled_round_with_in_cohort_faults(prob):
    """Drops/stragglers inside the cohort follow the usual fault
    bookkeeping while unsampled clients keep plain gaps."""
    srv = mk_server(prob, n_clients=6, frac=0.5)
    fp = FaultPlan(6, 8, drop_rate=0.4, late_rate=0.3, max_staleness=2,
                   seed=2)
    for r in range(8):
        srv.run_round(gp_vec=prob["gp"],
                      faults=fp.round_faults(srv.round))
        info = srv.last_round_info
        assert set(info["drops"]) <= set(info["cohort"])
        assert set(info["late"]) <= set(info["cohort"])


# -- bit-parity: frac=1.0 + identity codec == the dense round ---------------

def test_full_participation_identity_codec_bit_parity(prob):
    """An explicit full-fleet sampler + explicit IdentityCodec reproduce
    the default dense round bit-exactly — params, GradIP, CommLog."""
    dense = mk_server(prob, n_clients=4)
    assert dense.sampler is None and dense.codec.spec == "none"
    fleet = mk_server(
        prob, n_clients=4,
        sampler=ClientSampler(range(4), frac=1.0, seed=0),
        codec=IdentityCodec())
    assert fleet.sampler.m == 4
    for _ in range(3):
        dense.run_round(gp_vec=prob["gp"])
        fleet.run_round(gp_vec=prob["gp"])
    assert_servers_equal(dense, fleet)
    assert fleet.last_round_info["cohort"] == [0, 1, 2, 3]
    assert fleet.last_round_info["n_unsampled"] == 0


def test_full_participation_bit_parity_sharded(prob):
    """Same parity under a 1x1 FLShardPlan: the sampled/codec plumbing
    is mesh-neutral (DESIGN.md §9 composed with §12)."""
    from repro.sharding.fl import make_fl_plan
    plan = make_fl_plan(spec="1x1")
    dense = mk_server(prob, n_clients=4)
    fleet = mk_server(
        prob, n_clients=4, plan=plan,
        sampler=ClientSampler(range(4), frac=1.0, seed=0),
        codec=IdentityCodec())
    for _ in range(2):
        dense.run_round(gp_vec=prob["gp"])
        fleet.run_round(gp_vec=prob["gp"])
    assert_servers_equal(dense, fleet)


# -- quantized uplink --------------------------------------------------------

def test_quantized_round_exact_replay(prob):
    """The round's returned scalars are on the wire grid: the server's
    deterministic re-encode is lossless, and the virtual path
    reconstructed from the *wire payload alone* bit-matches the dense
    reconstruction from the decoded scalars."""
    srv = mk_server(prob, n_clients=3, quantize="int8")
    T = srv.fl.local_steps
    gs = srv.run_round()
    assert srv.codec.spec == "int8"
    for cid, g in gs.items():
        w = srv.codec.encode(g)  # nearest re-encode of on-grid values
        np.testing.assert_array_equal(srv.codec.decode(w), g)
        keys = round_keys(srv.fl.seed, 0, T)
        via_wire = VP.reconstruct_from_wire(prob["space"], keys, w,
                                            srv.codec, srv.fl.lr)
        direct = VP.reconstruct_delta(prob["space"], keys, jnp.asarray(g),
                                      srv.fl.lr)
        np.testing.assert_array_equal(np.asarray(via_wire),
                                      np.asarray(direct))


def test_quantized_uplink_bytes_and_effect(prob):
    """int8 halves the f32 uplink (1 code + 1 exponent byte per scalar
    at chunk=1) and actually changes the trajectory; downlink is
    untouched."""
    T = 2
    dense = mk_server(prob, n_clients=3, T=T)
    quant = mk_server(prob, n_clients=3, T=T, quantize="int8")
    dense.run_round()
    quant.run_round()
    assert dense.comm.up_bytes == 3 * 4 * T
    assert quant.comm.up_bytes == 3 * 2 * T
    assert dense.comm.down_bytes == quant.comm.down_bytes
    assert not np.array_equal(flat(dense.params), flat(quant.params))


def test_quantized_loop_matches_codec_grid(prob):
    """The compiled T=1 burst with a QuantSpec emits per-step scalars
    that the host codec reproduces bit-exactly (jax<->host grid parity
    inside the real train loop)."""
    codec = IntCodec(bits=8, stochastic=True)
    loop = make_fl_train_loop(prob["per_example"], prob["space"], eps=1e-3,
                              lr=1e-2, n_clients=4, n_steps=3,
                              quantize=codec.jax_spec())
    batch = sample_dataset(SPEC, 4 * 2 * 3, seed=0)
    batches = {k: jnp.asarray(v).reshape(3, 4 * 2, *np.shape(v)[1:])
               for k, v in batch.items()}
    _, gs, _ = jax.jit(loop)(prob["params"], jax.random.key(1), batches)
    gs = np.asarray(gs)
    np.testing.assert_array_equal(codec.decode(codec.encode(gs)), gs)


def test_loop_report_masks_all_ones_is_dense(prob):
    """report_masks as a runtime operand: all-ones masks match the
    maskless loop bitwise (one compiled program for every cohort)."""
    loop = make_fl_train_loop(prob["per_example"], prob["space"], eps=1e-3,
                              lr=1e-2, n_clients=4, n_steps=2)
    batch = sample_dataset(SPEC, 4 * 2 * 2, seed=0)
    batches = {k: jnp.asarray(v).reshape(2, 4 * 2, *np.shape(v)[1:])
               for k, v in batch.items()}
    jloop = jax.jit(loop)
    p0, g0, _ = jloop(prob["params"], jax.random.key(1), batches)
    p1, g1, _ = jloop(prob["params"], jax.random.key(1), batches,
                      jnp.ones((2, 4), jnp.float32))
    np.testing.assert_array_equal(flat(p0), flat(p1))
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
    # masking out half the clients changes the aggregate
    p2, _, _ = jloop(prob["params"], jax.random.key(1), batches,
                     jnp.asarray([[1, 1, 0, 0], [1, 1, 0, 0]], jnp.float32))
    assert not np.array_equal(flat(p0), flat(p2))


# -- checkpoint/resume under sampling + quantization -------------------------

def test_sampled_quantized_resume_bitexact(prob, tmp_path):
    """Save at round 2 of a sampled+quantized run, restore into a fresh
    server, continue: bit-identical to the uninterrupted run — including
    the sampler's re-drawn cohorts (RNG state restore)."""
    path = str(tmp_path / "ckpt.msgpack")
    ref = mk_server(prob, frac=0.5, quantize="int8")
    cohorts_ref = []
    for _ in range(5):
        ref.run_round(gp_vec=prob["gp"])
        cohorts_ref.append(ref.last_round_info["cohort"])
    donor = mk_server(prob, frac=0.5, quantize="int8")
    for _ in range(2):
        donor.run_round(gp_vec=prob["gp"])
    donor.save_checkpoint(path)
    fresh = mk_server(prob, frac=0.5, quantize="int8")
    meta = fresh.load_checkpoint(path)
    assert meta["round"] == 2 and meta["sampler"] is not None
    cohorts_resumed = []
    for _ in range(3):
        fresh.run_round(gp_vec=prob["gp"])
        cohorts_resumed.append(fresh.last_round_info["cohort"])
    assert cohorts_resumed == cohorts_ref[2:]
    assert_servers_equal(ref, fresh)
    assert fresh.sampler.state_dict() == ref.sampler.state_dict()


def test_sampler_presence_mismatch_refused(prob, tmp_path):
    path = str(tmp_path / "ckpt.msgpack")
    donor = mk_server(prob, frac=0.5)
    donor.run_round()
    donor.save_checkpoint(path)
    dense = mk_server(prob)  # no sampler: config fingerprint differs
    with pytest.raises(CheckpointError):
        dense.load_checkpoint(path)


# -- O(seeds + scalars) server state -----------------------------------------

def test_server_state_o1_in_fleet_size(prob):
    """Growing K grows only the per-client scalar bookkeeping (a few
    bytes per client), never the model-sized state — the argument that
    lets one server host thousands of ZO clients."""
    small = mk_server(prob, n_clients=4, frac=0.5)
    big = mk_server(prob, n_clients=32, frac=0.5)
    for _ in range(2):
        small.run_round(gp_vec=prob["gp"])
        big.run_round(gp_vec=prob["gp"])
    a, b = server_state_sizes(small), server_state_sizes(big)
    assert a["model_state_bytes"] == b["model_state_bytes"]
    # per-client bookkeeping stays tiny: pointers + a few logged scalars
    per_client = b["per_client_state_bytes"] / b["n_clients"]
    assert per_client < 1024
