"""Zero-downtime serving (stream mode, DESIGN.md §15) tests:

  * ``serve.generate`` accumulates tokens on device and transfers ONCE —
    bit-exact vs a per-step-sync replica of the pre-fix loop;
  * shadow-sweep drains are bit-exact vs the legacy in-place sweep, and
    ``publish_staged`` is an atomic pointer swap;
  * publication atomicity via a poisoned-shadow probe: with a NaN-filled
    tree published mid-stream at the deterministic step deadline, every
    token written BEFORE the publish step equals the drain-free reference,
    the poison signature appears only at/after it, and the decode program
    never recompiles across the publication;
  * a drain fired between every decode step: the final published params
    are bit-identical to the same drains applied sequentially in place,
    publications == drain groups, zero warm recompiles;
  * DrainScheduler: negative queue ages are clamped to 0 with a
    ``queue.age_skew`` event, ``submit(now=-1)`` raises, and
    ``pending_entries`` is the public queue view (folded entries expand);
  * the stream engine's outputs match the legacy batched ``generate``
    loop, and staggered-admission runs are deterministic;
  * admission prefills a prompt of up to ``layers.Q_CHUNK`` tokens in ONE
    launch (longer ones in equal divisor blocks), wide or scanned, with
    the greedy outputs of the token-wise decode walk.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.api import ServeSpec
from repro.data import synthetic as syn
from repro.fleet import DrainScheduler
from repro.launch.serve import (ForgetService, StreamEngine,
                                _trees_bitwise_equal,
                                admission_prefill_block, engine_fingerprint,
                                generate)
from repro.models import lm as LM
from repro.obs import telemetry as _t

P, G = 8, 6
SEQ = P + G


@pytest.fixture(scope="module")
def cfg():
    return LM.LMConfig(name="stream-t", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=2, d_ff=64, vocab=64)


@pytest.fixture(scope="module")
def data(cfg):
    dcfg = syn.LMDataConfig(vocab=cfg.vocab, n_domains=4, seq_len=SEQ,
                            n_per_domain=8, seed=0)
    toks, doms = syn.make_lm_domains(dcfg)
    params = LM.init_lm(jax.random.PRNGKey(0), cfg)
    return toks, doms, params


def _decode_jit(cfg):
    return jax.jit(lambda p, c, t, pos: LM.decode_step(p, cfg, t, c, pos))


def _svc(cfg, data, programs=None, **serve_kw):
    toks, doms, _ = data
    return ForgetService(cfg, toks, doms, SEQ, programs=programs,
                         serve=ServeSpec(chunk_size=4, **serve_kw))


# -- satellite: single-transfer generate --------------------------------------

def _generate_per_step_sync(params, cfg, prompts, gen_len, decode_jit,
                            prefill_block=8):
    """Replica of the pre-fix loop: a blocking np.asarray every step."""
    B, Plen = prompts.shape
    cache = LM.init_cache(cfg, B, Plen + gen_len)
    logits, cache = LM.prefill(params, cfg, prompts, cache,
                               block=prefill_block)
    out = []
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    for j in range(gen_len):
        out.append(np.asarray(tok)[:, 0])
        logits, cache = decode_jit(params, cache, tok, jnp.int32(Plen + j))
        tok = jnp.argmax(logits[:, -1:], axis=-1)
    return np.stack(out, axis=1)


def test_generate_single_transfer_bit_exact(cfg, data):
    toks, _, params = data
    dj = _decode_jit(cfg)
    prompts = jnp.asarray(toks[:4, :P])
    got = generate(params, cfg, prompts, G, dj)
    want = _generate_per_step_sync(params, cfg, prompts, G, dj)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


# -- shadow sweep bit-exactness + atomic swap ---------------------------------

def test_shadow_sweep_bit_exact_and_atomic_swap(cfg, data):
    _, _, params = data
    svc_a = _svc(cfg, data)
    svc_b = _svc(cfg, data, programs=svc_a._fleet.programs)

    # legacy in-place drain
    svc_a.submit(1, due_batch=0)
    legacy, ran_a = svc_a.drain(params, 0)
    assert ran_a

    # shadow drain: the live pointer must not move until publication
    svc_b.install_params(params)
    shadow, ran_b = svc_b.run_shadow([1], 0)
    assert ran_b
    assert svc_b.params is params          # live tree untouched
    assert svc_b.params_version == 0
    assert _trees_bitwise_equal(shadow, legacy)

    svc_b.stage(shadow)
    assert svc_b.publish_staged(step=7)    # atomic pointer swap
    assert svc_b.params is shadow
    assert svc_b.params_version == 1
    assert not svc_b.publish_staged(step=8)   # nothing staged -> no-op

    # the rerouted legacy queue property (public pending_entries path)
    svc_b.submit(3, due_batch=9)
    assert list(svc_b.queue) == [{"domain": 3, "due_batch": 9}]


# -- publication atomicity: the poisoned-shadow probe -------------------------

def _run_stream(params, cfg, data, n_seq, svc=None, publish_lag=3):
    eng = StreamEngine(params, cfg, gen_len=G, prompt_len=P,
                       max_batch=4, admit_chunk=2,
                       publish_lag=publish_lag, service=svc)
    toks = data[0]
    prompts = np.asarray(toks[:, :P])
    for i in range(n_seq):
        eng.enqueue(i, prompts[i % len(prompts)])
    with _t.capture() as cap:
        out = eng.run()
    return eng, out, cap.events


def test_publication_atomicity_poisoned_shadow(cfg, data):
    _, _, params = data
    n_seq = 10

    # drain-free reference: same traffic, no service
    _, ref, _ = _run_stream(params, cfg, data, n_seq)

    poisoned = jax.tree_util.tree_map(
        lambda a: jnp.full_like(a, jnp.nan)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)

    svc = _svc(cfg, data)
    svc.run_shadow = lambda payloads, step: (poisoned, True)
    svc.submit(1, due_batch=2)
    eng, got, events = _run_stream(params, cfg, data, n_seq, svc=svc)

    pubs = [e for e in events if e["kind"] == "params.publish"]
    assert len(pubs) == 1 and eng.publications == 1
    s_pub = pubs[0]["step"]
    assert s_pub == 2 + 3                  # fire step + publish_lag
    assert svc.params is poisoned and eng.params is poisoned

    # token j of a sequence admitted at step s_a is written at step
    # s_a + j; every token written BEFORE the publish step must equal the
    # drain-free reference (no step observed a half-installed tree), and
    # the poison signature must show up at/after it for some sequence
    admit_step = {}
    for e in events:
        if e["kind"] == "batch.admit":
            for sid in e["seqs"]:
                admit_step[sid] = e["step"]
    assert set(admit_step) == set(range(n_seq))
    poisoned_suffix_seen = False
    for sid in range(n_seq):
        pre = max(0, min(G, s_pub - admit_step[sid]))
        np.testing.assert_array_equal(got[sid][:pre], ref[sid][:pre])
        if pre < G and not np.array_equal(got[sid][pre:], ref[sid][pre:]):
            poisoned_suffix_seen = True
    assert poisoned_suffix_seen

    # publication must replay the ONE warm decode program: zero recompiles
    assert eng.decode_cache_size() == 1


# -- a drain between every decode step ----------------------------------------

def test_drain_every_step_chains_bit_exact(cfg, data):
    _, _, params = data
    svc = _svc(cfg, data)
    for k in range(4):
        svc.submit(1 + (k % 2), due_batch=k)   # one drain due EVERY step
    eng, got, events = _run_stream(params, cfg, data, 8, svc=svc,
                                   publish_lag=1)
    assert len(got) == 8
    assert svc.groups == 4
    assert eng.publications == 4 and svc.params_version == 4
    assert eng.decode_cache_size() == 1        # zero warm recompiles
    pubs = [e for e in events if e["kind"] == "params.publish"]
    assert [p["version"] for p in pubs] == [1, 2, 3, 4]

    # the chained shadow sweeps must be bit-identical to the same drains
    # applied sequentially IN PLACE (the legacy path), in fire order
    svc2 = _svc(cfg, data, programs=svc._fleet.programs)
    rt2 = svc2._rt
    replay = params
    for g in svc.group_log:
        replay, ran = rt2.run_due(replay, g["domains"], g["batch"])
        assert ran
    assert _trees_bitwise_equal(svc.params, replay)


# -- scheduler: age clamp, skew event, public queue view ----------------------

def test_scheduler_age_clamp_and_skew_event():
    s = DrainScheduler("deadline")
    s.register("t")
    s.submit("t", 1, 5, now=10)            # clock skew: "future" submission
    with _t.capture() as cap:
        assert s.oldest_age("t", 3) == 0   # clamped, never negative
    skews = [e for e in cap.events if e["kind"] == "queue.age_skew"]
    assert len(skews) == 1 and skews[0]["raw_age"] == -7
    with _t.capture() as cap:
        (group,) = s.due_groups(6)
    assert group.ages == (0,)              # clamped in the drain decision
    assert any(e["kind"] == "queue.age_skew" for e in cap.events)
    with pytest.raises(ValueError, match="now="):
        s.submit("t", 1, 5, now=-1)


def test_pending_entries_public_view():
    s = DrainScheduler("deadline", max_queue=1, admission="defer")
    s.register("t")
    s.submit("t", 1, 3, now=0)
    s.submit("t", 2, 5, now=1)             # folds into the oldest entry
    assert s.pending_entries("t") == [
        {"payload": 1, "due_batch": 3, "submitted": 0},
        {"payload": 2, "due_batch": 3, "submitted": 0}]
    assert s.pending_entries("unknown") == []


# -- stream vs batch generate + staggered determinism -------------------------

def test_stream_matches_batch_generate(cfg, data):
    toks, _, params = data
    B = 4
    prompts = np.asarray(toks[:B, :P])
    ref = generate(params, cfg, jnp.asarray(prompts), G, _decode_jit(cfg))
    eng = StreamEngine(params, cfg, gen_len=G, prompt_len=P,
                       max_batch=B, admit_chunk=B)
    for i in range(B):
        eng.enqueue(i, prompts[i])
    got = eng.run()
    assert sorted(got) == list(range(B))
    for i in range(B):
        np.testing.assert_array_equal(got[i], ref[i])


def test_staggered_stream_deterministic(cfg, data):
    _, _, params = data
    n_seq = 10
    runs = [_run_stream(params, cfg, data, n_seq) for _ in range(2)]
    (_, out_a, ev_a), (_, out_b, ev_b) = runs
    assert sorted(out_a) == list(range(n_seq))
    for sid in range(n_seq):
        np.testing.assert_array_equal(out_a[sid], out_b[sid])
    # engine_fingerprint drops the cross-thread seq counter: sweep worker
    # events shift engine seq values at scheduler-dependent points
    fp = [engine_fingerprint(ev) for ev in (ev_a, ev_b)]
    assert fp[0] == fp[1]


# -- admission: one prefill launch per admission ------------------------------

@pytest.mark.parametrize("arch_id,Plen,S_max,block,wide", [
    ("internvl2-1b", 512, 640, 512, True),   # the cell: one wide block
    ("internvl2-1b", 1000, 1128, 500, True),  # past Q_CHUNK: equal divisors
    ("internvl2-1b", 1536, 1664, 512, True),
    ("gemma3-1b", 24, 30, 24, False),       # window 16 < P: the scan path
])
def test_admission_prefill_block(arch_id, Plen, S_max, block, wide):
    cfg = configs.get(arch_id).smoke
    assert admission_prefill_block(Plen) == block
    assert Plen % block == 0
    sub = jax.eval_shape(lambda: LM.init_cache(cfg, 4, S_max))
    assert (Plen <= LM._min_attn_cache(cfg, sub)) == wide


def _tokenwise_greedy(params, cfg, prompts, gen_len):
    """Greedy continuation after feeding the prompt one decode step per
    token — the walk every prefill mode must reproduce."""
    B, Plen = prompts.shape
    dj = _decode_jit(cfg)
    cache = LM.init_cache(cfg, B, Plen + gen_len)
    for i in range(Plen):
        logits, cache = dj(params, cache, prompts[:, i:i + 1], jnp.int32(i))
    out = []
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    for j in range(gen_len):
        out.append(np.asarray(tok)[:, 0])
        logits, cache = dj(params, cache, tok, jnp.int32(Plen + j))
        tok = jnp.argmax(logits[:, -1:], axis=-1)
    return np.stack(out, axis=1)


@pytest.mark.parametrize("arch_id", [None, "gemma3-1b"])
def test_stream_admits_in_one_launch(cfg, arch_id):
    """A 24-token prompt is admitted in ONE prefill launch — wide on the
    dense tiny config, a scan of decode steps where gemma3's 16-slot
    window cache wraps — and every sequence decodes what the token-wise
    walk decodes."""
    if arch_id is not None:
        cfg = configs.get(arch_id).smoke
    Plen, n_seq = 24, 5
    params = LM.init_lm(jax.random.PRNGKey(1), cfg)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (n_seq, Plen), 0, cfg.vocab))
    eng = StreamEngine(params, cfg, gen_len=G, prompt_len=Plen,
                       max_batch=4, admit_chunk=2)
    assert eng.prefill_block == Plen
    for i in range(n_seq):
        eng.enqueue(i, prompts[i])
    got = eng.run()
    st = eng.stats()
    assert st["admissions"] == 3
    assert st["prefill_launches"] == st["admissions"]
    want = _tokenwise_greedy(params, cfg, jnp.asarray(prompts), G)
    for i in range(n_seq):
        np.testing.assert_array_equal(got[i], want[i])
