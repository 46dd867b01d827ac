"""Batched slot executor: vmapped rounds, per-slot KV positions, fused
coded decode, SLO admission.

The tier-1 properties of the one-dispatch-per-round engine:
  (a) the stacked round is token-for-token identical to sequential
      per-slot stepping across staggered admission (slots at different KV
      positions), with and without host/device overlap;
  (b) every in-budget erasure index under the batched round still yields
      exact logits (the paper's close-to-zero recovery, pool-wide);
  (c) the Pallas fused coded-head decode matches the reference decode on
      the (T, r) grid;
plus: a scheduler round with n_slots >= 4 issues ONE jitted dispatch (no
per-slot stepping on the hot path), and the deadline/shedding admission
queue orders and bounds correctly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.kernels import ops, ref
from repro.kernels.cdc_decode import pad_head_shards
from repro.models import TPCtx, build
from repro.runtime import (AdmissionQueue, ContinuousBatchingScheduler,
                           Request, RequestState, RuntimeConfig,
                           ShardHealthController, erasure, run_arrivals)
from repro.runtime.executor import (SlotPoolExecutor, VStep, read_slot,
                                    stack_states, supports_slot_batching,
                                    unstack_states, write_slot)
from repro.serve import ModelStepper, ServeConfig, ServingEngine

GEN = 5
T, R = 4, 2


@pytest.fixture(scope="module")
def coded():
    cfg = smoke_config(get_arch("granite-3-8b"))
    model = build(cfg, TPCtx(tp=T, mode="coded", code_r=R, moe_capacity=0))
    params = model.init(jax.random.PRNGKey(0))
    stepper = ModelStepper(model, params, max_len=48)
    return cfg, stepper


def _staggered(cfg, n, base_len=4):
    """Prompts of different lengths arriving at different times — slots
    end up at genuinely different KV positions."""
    rng = np.random.default_rng(3)
    return [(i * 1.5, rng.integers(0, cfg.vocab, base_len + i % 4), GEN)
            for i in range(n)]


def _serve(stepper, arrivals, *, batched, n_slots=4, overlap=True,
           events=(), use_fused="auto"):
    health = ShardHealthController(stepper.n_shards, stepper.erasure_budget,
                                   events=list(events))
    sched = ContinuousBatchingScheduler(
        stepper, RuntimeConfig(n_slots=n_slots, batched=batched,
                               overlap=overlap, use_fused=use_fused),
        health=health)
    done = run_arrivals(sched, [(t, p, n) for t, p, n in arrivals])
    return sched, {r.rid: r.tokens for r in done}


# ------------------------------------------------- (a) round equivalence ----

def test_batched_round_matches_sequential_staggered(coded):
    """Stacked one-dispatch rounds == sequential per-slot stepping,
    token for token, with slots admitted at different KV positions —
    in both overlap modes."""
    cfg, stepper = coded
    arrivals = _staggered(cfg, 6)
    s_seq, toks_seq = _serve(stepper, arrivals, batched=False)
    s_b, toks_b = _serve(stepper, arrivals, batched=True, overlap=True)
    s_bn, toks_bn = _serve(stepper, arrivals, batched=True, overlap=False)
    assert len(toks_seq) == 6
    assert toks_b == toks_seq
    assert toks_bn == toks_seq
    assert all(len(t) == GEN for t in toks_b.values())
    # both executions measured real round latency
    assert len(s_b.metrics.round_ms) > 0
    assert len(s_seq.metrics.round_ms) > 0


def test_one_round_is_one_dispatch(coded):
    """n_slots >= 4: a decode round is ONE jitted dispatch for the whole
    pool — one trace ever, dispatches == rounds, and the per-slot
    ``decode_one`` stepper is never touched on the hot path."""
    cfg, stepper = coded
    calls = {"decode_one": 0}
    orig = stepper.decode_one
    stepper.decode_one = lambda *a, **k: calls.__setitem__(
        "decode_one", calls["decode_one"] + 1) or orig(*a, **k)
    try:
        sched, toks = _serve(stepper, _staggered(cfg, 8), batched=True,
                             n_slots=4)
    finally:
        stepper.decode_one = orig
    assert calls["decode_one"] == 0, "per-slot Python-loop stepping on " \
                                     "the batched hot path"
    vstep = sched.executor.vstep
    assert vstep.n_traces == 1, "round retraced: admission/mask changed " \
                                "compiled shapes"
    assert vstep.n_dispatches == sched.metrics.counters["decode_rounds"]
    assert sched.metrics.counters["requests_completed"] == 8


def test_slot_write_read_roundtrip(coded):
    cfg, stepper = coded
    rng = np.random.default_rng(0)
    ex = SlotPoolExecutor(stepper, n_slots=3, overlap=False)
    mask = np.ones(T, bool)
    prompt = rng.integers(0, cfg.vocab, 6)
    ex.admit(1, prompt, mask, tag="x")
    row = read_slot(ex.state, 1)
    # the written row really sits at slot 1 with its own position vector
    assert int(row["kv"]["len"][0, 0]) == len(prompt)
    assert int(read_slot(ex.state, 0)["kv"]["len"][0, 0]) == 0
    back = write_slot(ex.state, 2, row)
    assert int(jax.tree.leaves({"l": back["kv"]["len"]})[0][0][2]) \
        == len(prompt)
    # unstack -> stack is the identity on the slot axis
    restacked = stack_states(unstack_states(ex.state, 3))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 ex.state, restacked)


# --------------------------------------------- (b) erasure exact logits ----

def test_every_inbudget_erasure_exact_logits(coded):
    """Each erasable shard index under the batched round: logits of the
    whole stacked pool match the fault-free round exactly (recovery
    in-step, for every slot at once)."""
    cfg, stepper = coded
    rng = np.random.default_rng(1)
    ex = SlotPoolExecutor(stepper, n_slots=4, overlap=False)
    full = np.ones(T, bool)
    for i, plen in enumerate((4, 6, 7, 5)):     # staggered KV positions
        ex.admit(i, rng.integers(0, cfg.vocab, plen), full, tag=i)
    vstep = ex.vstep
    _, toks_ok, logits_ok = vstep.round(ex.state, ex.last_toks, full)
    assert logits_ok is not None
    for shard in range(T):
        mask = full.copy()
        mask[shard] = False
        _, toks_f, logits_f = vstep.round(ex.state, ex.last_toks, mask)
        np.testing.assert_allclose(np.asarray(logits_f),
                                   np.asarray(logits_ok),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"shard {shard}")
        np.testing.assert_array_equal(np.asarray(toks_f),
                                      np.asarray(toks_ok))


def test_scheduler_erasure_stream_identical(coded):
    """Mid-stream erasure through the batched scheduler: same tokens as
    the fault-free run, recovered in-step, nothing requeued."""
    cfg, stepper = coded
    arrivals = _staggered(cfg, 4)
    _, toks_ok = _serve(stepper, arrivals, batched=True)
    s_f, toks_f = _serve(stepper, arrivals, batched=True,
                         events=[erasure(2.0, 1)])
    assert toks_f == toks_ok
    assert s_f.metrics.counters["erasures_recovered"] == 1
    assert s_f.metrics.counters["requests_requeued"] == 0


# ------------------------------------------------ (c) fused Pallas head ----

@pytest.mark.parametrize("t", [2, 4])
@pytest.mark.parametrize("r", [1, 2])
def test_fused_head_matches_reference_grid(t, r):
    """Pallas fused coded-matmul + parity-decode + argmax == reference
    decode, fault-free and under every single erasure (any r >= 1 carries
    the all-ones sum parity the fused kernel consumes)."""
    rng = np.random.default_rng(t * 10 + r)
    b, k, m = 3, 32, 8 * t * t
    x = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(t, k, m // t)), jnp.float32)
    pw = w.sum(0)
    merged = jnp.moveaxis(jnp.einsum("bk,tkn->tbn", x, w), 0, -2)
    truth = jnp.argmax(merged.reshape(b, -1), -1)
    for dead in [None] + list(range(t)):
        valid = jnp.ones(t, bool)
        if dead is not None:
            valid = valid.at[dead].set(False)
        tok, val = ops.fused_head_argmax(x, w, pw, valid, vocab=m)
        rtok, rval = ref.fused_head_argmax_ref(x, w, pw, valid, m)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(rtok))
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(truth))
        np.testing.assert_allclose(np.asarray(val), np.asarray(rval),
                                   rtol=1e-5)


@pytest.mark.parametrize("m_l", [40, 200])
def test_fused_head_lane_padded_shards(m_l):
    """Head shards zero-padded to whole 128-lane tiles (what the executor
    caches) give the same token and max logit as the unpadded shards,
    and the padding columns never win, even when every logit is
    negative."""
    t, b, k = 4, 3, 32
    rng = np.random.default_rng(m_l)
    x = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(t, k, m_l)) - 3.0, jnp.float32)
    vocab = t * m_l - 3
    wp, pwp = pad_head_shards(w, w.sum(0))
    assert wp.shape[-1] % 128 == 0 and pwp.shape[-1] == wp.shape[-1]
    for valid in (jnp.ones(t, bool), jnp.asarray([True, False, True, True])):
        tok, val = ops.fused_head_argmax(x, w, w.sum(0), valid, vocab=vocab)
        ptok, pval = ops.fused_head_argmax(x, wp, pwp, valid, vocab=vocab,
                                           shard_width=m_l)
        rtok, rval = ref.fused_head_argmax_ref(x, wp, pwp, valid, vocab,
                                               shard_width=m_l)
        np.testing.assert_array_equal(np.asarray(ptok), np.asarray(tok))
        np.testing.assert_array_equal(np.asarray(rtok), np.asarray(tok))
        np.testing.assert_allclose(np.asarray(pval), np.asarray(val),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(rval), np.asarray(val),
                                   rtol=1e-5)


def test_fused_round_matches_reference_round(coded):
    """End-to-end: the fused-head batched round produces the same next
    tokens as the reference (full-logits) round, fault-free and with one
    erased shard."""
    cfg, stepper = coded
    rng = np.random.default_rng(5)
    ex = SlotPoolExecutor(stepper, n_slots=4, overlap=False)
    full = np.ones(T, bool)
    for i, plen in enumerate((4, 6, 7, 5)):
        ex.admit(i, rng.integers(0, cfg.vocab, plen), full, tag=i)
    ref_step = VStep(stepper, use_fused=False)
    fused_step = VStep(stepper, use_fused=True)
    assert fused_step.use_fused, "fused path must be available for the " \
                                 "coded transformer"
    for mask in (full, np.array([True, False, True, True])):
        _, toks_ref, _ = ref_step.round(ex.state, ex.last_toks, mask)
        _, toks_fused, logits = fused_step.round(ex.state, ex.last_toks,
                                                 mask)
        assert logits is None, "fused round must not materialise logits"
        np.testing.assert_array_equal(np.asarray(toks_fused),
                                      np.asarray(toks_ref))


def test_fused_falls_back_beyond_eq12(coded):
    """Two dead shards exceed the sum-parity regime: the fused executor
    silently uses the reference MDS path (and still returns logits)."""
    cfg, stepper = coded
    rng = np.random.default_rng(6)
    ex = SlotPoolExecutor(stepper, n_slots=2, overlap=False)
    full = np.ones(T, bool)
    ex.admit(0, rng.integers(0, cfg.vocab, 4), full, tag=0)
    fused_step = VStep(stepper, use_fused=True)
    mask2 = np.array([True, False, False, True])
    _, _, logits = fused_step.round(ex.state, ex.last_toks, mask2)
    assert logits is not None


# ------------------------------------------------- legacy facade parity ----

def test_serving_engine_delegates_to_executor(coded):
    """The deprecated ServingEngine facade and the raw sequential stepper
    loop agree token-for-token — the facade can't silently diverge from
    the batched path it now delegates to."""
    cfg, stepper = coded
    model = stepper.model
    eng = ServingEngine(model, stepper.params,
                        ServeConfig(max_len=48, batch=2,
                                    cache_dtype=jnp.float32))
    batch = model.dummy_batch(jax.random.PRNGKey(1), 2, 8)
    got = eng.generate(batch, 6, fail_at={2: 1})
    eng2 = ServingEngine(model, stepper.params,
                         ServeConfig(max_len=48, batch=2,
                                     cache_dtype=jnp.float32))
    eng2.inject_failure(1)  # pre-kill so the sequential run sees the same
    eng2.metrics["erasures_recovered"] = 0
    want_pre = eng2._generate_sequential(batch, 6, fail_at=None)
    # tokens after the injection step must match the always-degraded run;
    # before it, the healthy run (coded recovery is exact either way)
    healthy = ServingEngine(model, stepper.params,
                            ServeConfig(max_len=48, batch=2,
                                        cache_dtype=jnp.float32))
    want_ok = healthy._generate_sequential(batch, 6, fail_at=None)
    np.testing.assert_array_equal(got, want_ok)
    np.testing.assert_array_equal(got, want_pre)


# ------------------------------------------------------- SLO admission ----

def _req(rid, arrival=0.0, deadline=None, priority=0):
    return Request(rid, np.array([1], np.int32), 1, arrival_ms=arrival,
                   deadline_ms=deadline, priority=priority)


def test_admission_queue_deadline_order():
    q = AdmissionQueue()
    q.push(_req(0, arrival=0.0))                      # best effort
    q.push(_req(1, arrival=1.0, deadline=50.0))
    q.push(_req(2, arrival=2.0, deadline=10.0))
    q.push(_req(3, arrival=3.0, priority=1))          # priority trumps all
    assert [q.pop().rid for _ in range(4)] == [3, 2, 1, 0]


def test_admission_queue_fifo_when_unconfigured():
    q = AdmissionQueue()
    for i, t in enumerate((0.0, 1.0, 2.0)):
        q.push(_req(i, arrival=t))
    # a 2MR requeue keeps its original arrival and re-enters ahead
    q.push(_req(9, arrival=0.5), force=True)
    assert [q.pop().rid for _ in range(4)] == [0, 9, 1, 2]


def test_admission_queue_sheds_worst():
    q = AdmissionQueue(max_depth=2)
    assert q.push(_req(0, deadline=10.0)) is None
    assert q.push(_req(1, deadline=20.0)) is None
    shed = q.push(_req(2, deadline=5.0))   # tightest deadline stays
    assert shed is not None and shed.rid == 1
    assert q.push(_req(3, deadline=99.0)).rid == 3   # incoming is worst
    assert len(q) == 2
    with pytest.raises(ValueError):
        AdmissionQueue(max_depth=0)


def test_admission_queue_never_sheds_requeued_work():
    """A 2MR-requeued (once-admitted) request is protected from shedding
    even as the victim of a LATER push — 'never loses a request' holds
    for admitted work under any queue pressure."""
    q = AdmissionQueue(max_depth=1)
    requeued = _req(0)                  # worst-ordered: no deadline
    requeued.n_requeues = 1
    q.push(requeued, force=True)
    fresh = _req(1, deadline=5.0)       # sorts BEFORE the requeued one
    shed = q.push(fresh)
    assert shed is not None and shed.rid == 1, \
        "the sheddable newcomer must be dropped, not the admitted request"
    assert [r.rid for r in q] == [0]
    # all-protected queue: the bound yields rather than shedding
    q2 = AdmissionQueue(max_depth=1)
    for rid in (0, 1):
        r = _req(rid)
        r.n_requeues = 1
        assert q2.push(r, force=True) is None
    assert q2.push(_req(2, deadline=1.0)).rid == 2
    assert len(q2) == 2


def test_scheduler_sheds_and_reports(coded):
    """Queue-depth bound under a burst: shed count and queue depth land in
    RuntimeMetrics; everything admitted still completes."""
    cfg, stepper = coded
    sched = ContinuousBatchingScheduler(
        stepper, RuntimeConfig(n_slots=1, max_queue_depth=2))
    rng = np.random.default_rng(2)
    reqs = [sched.submit(rng.integers(0, cfg.vocab, 4), 2,
                         deadline_ms=100.0 + i) for i in range(6)]
    done = sched.run()
    c = sched.metrics.counters
    # all 6 land before the first round: the bound keeps 2, sheds 4
    assert c["requests_shed"] == 4 == len(sched.shed)
    assert all(r.state is RequestState.SHED for r in sched.shed)
    assert c["requests_completed"] == len(done) == 2
    assert c["requests_submitted"] == 6
    snap = sched.metrics.snapshot()
    assert snap["queue_depth"]["max"] <= 2
    # the survivors are the earliest deadlines (first-come here)
    assert {r.rid for r in done} == {0, 1}


def test_deadline_reorders_admission(coded):
    """A later-arriving tighter-deadline request is admitted before an
    earlier best-effort one."""
    cfg, stepper = coded
    sched = ContinuousBatchingScheduler(
        stepper, RuntimeConfig(n_slots=1))
    rng = np.random.default_rng(4)
    p = lambda: rng.integers(0, cfg.vocab, 4)
    r_early = sched.submit(p(), 3)                    # FIFO, submitted 1st
    r_slow = sched.submit(p(), 2)
    r_urgent = sched.submit(p(), 2, deadline_ms=5.0)  # submitted LAST
    sched.run()
    # deadline-ordered pop: urgent wins the single slot outright
    assert r_urgent.admitted_ms < r_early.admitted_ms < r_slow.admitted_ms


# ----------------------------------------------------- support surface ----

def test_supports_slot_batching_universal():
    """Every zoo family slot-batches now (enc-dec via the extras bank,
    xLSTM via its positionless axis-0 block state); the detailed
    per-architecture equivalence lives in test_executor_conformance.py."""
    for arch in ("xlstm-125m", "whisper-medium", "granite-3-8b"):
        assert supports_slot_batching(build(smoke_config(get_arch(arch)),
                                            TPCtx()))


def test_sequential_oracle_survives_for_xlstm():
    """``batched=False`` keeps the sequential per-slot path alive as the
    differential-test oracle / --sequential escape hatch; the default is
    the batched executor even for xLSTM."""
    cfg = smoke_config(get_arch("xlstm-125m"))
    model = build(cfg, TPCtx())
    params = model.init(jax.random.PRNGKey(0))
    stepper = ModelStepper(model, params, max_len=32)
    sched = ContinuousBatchingScheduler(
        stepper, RuntimeConfig(n_slots=2, batched=False))
    assert sched.executor is None
    rng = np.random.default_rng(0)
    done = run_arrivals(sched, [(0.0, rng.integers(0, cfg.vocab, 4), 3),
                                (1.0, rng.integers(0, cfg.vocab, 4), 3)])
    assert len(done) == 2 and all(len(r.tokens) == 3 for r in done)
    auto = ContinuousBatchingScheduler(stepper, RuntimeConfig(n_slots=2))
    assert auto.executor is not None
