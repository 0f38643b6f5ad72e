"""Per-architecture smoke tests: every assigned arch instantiates its REDUCED
config, runs one forward + one train step + (where applicable) decode steps
on CPU, asserting output shapes and finiteness.  Also checks decode/forward
parity (a KV-cache bug shows up as divergence)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import encdec as ED
from repro.models import lm as LM
from repro.optim import AdamWConfig, init_adamw, make_train_step

ARCHS = sorted(configs.all_archs())


def _lm_batch(cfg, key, B=2, S=16):
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab)
    prefix = None
    if cfg.prefix_len:
        prefix = jax.random.normal(key, (B, cfg.prefix_len, cfg.d_model),
                                   jnp.float32)
    return toks[:, :-1], toks[:, 1:], prefix


@pytest.mark.parametrize("arch_id", ARCHS)
def test_forward_and_train_step(arch_id, key):
    spec = configs.get(arch_id)
    cfg = spec.smoke
    if spec.kind == "encdec":
        params = ED.init_encdec(key, cfg)
        B, S = 2, 12
        toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab)
        frames = jax.random.normal(key, (B, cfg.n_frames, cfg.d_model))
        logits = jax.jit(lambda p, t, f: ED.forward(p, cfg, t, f))(
            params, toks[:, :-1], frames)
        assert logits.shape == (B, S, cfg.vocab)
        assert bool(jnp.isfinite(logits).all())
        loss_fn = lambda p, b: ED.lm_loss(p, cfg, b[0], b[1], b[2])
        batch = (toks[:, :-1], toks[:, 1:], frames)
    else:
        params = LM.init_lm(key, cfg)
        toks, labels, prefix = _lm_batch(cfg, key)
        logits, aux = jax.jit(lambda p, t, px: LM.forward(p, cfg, t, px))(
            params, toks, prefix)
        S_out = toks.shape[1] + cfg.prefix_len
        assert logits.shape == (2, S_out, cfg.vocab)
        assert bool(jnp.isfinite(logits).all())
        assert bool(jnp.isfinite(aux))
        loss_fn = lambda p, b: LM.lm_loss(p, cfg, b[0], b[1], prefix=b[2])
        batch = (toks, labels, prefix)

    ocfg = AdamWConfig(lr=1e-3, total_steps=10)
    step = jax.jit(make_train_step(loss_fn, ocfg))
    opt = init_adamw(ocfg, params)
    p2, opt2, loss = step(params, opt, batch)
    assert bool(jnp.isfinite(loss))
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(p2))
        if jnp.issubdtype(a.dtype, jnp.floating))
    assert moved


@pytest.mark.parametrize("arch_id", [a for a in ARCHS
                                     if configs.get(a).kind == "lm"])
def test_decode_matches_forward(arch_id, key):
    """Greedy per-position logits from the decode path must match the full
    forward pass (validates KV caches, ring buffers, recurrent states)."""
    spec = configs.get(arch_id)
    cfg = spec.smoke
    if cfg.prefix_len:
        cfg = cfg.with_(prefix_len=0)   # parity check on the token backbone
    if cfg.moe is not None:
        # capacity dropping is a train-time batch effect; decode (1 token)
        # never drops — compare with a no-drop capacity factor.
        import dataclasses as dc
        cfg = cfg.with_(moe=dc.replace(cfg.moe,
                                       capacity_factor=float(cfg.moe.num_experts)))
    params = LM.init_lm(key, cfg)
    B, S = 2, 12
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    full_logits, _ = LM.forward(params, cfg, toks)

    cache = LM.init_cache(cfg, B, S)
    dec = jax.jit(lambda p, c, t, pos: LM.decode_step(p, cfg, t, c, pos))
    outs = []
    for i in range(S):
        lg, cache = dec(params, cache, toks[:, i:i + 1], jnp.int32(i))
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits),
                               rtol=2e-2, atol=2e-2)


def test_encdec_decode_matches_forward(key):
    spec = configs.get("whisper-tiny")
    cfg = spec.smoke
    params = ED.init_encdec(key, cfg)
    B, S = 2, 8
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    frames = jax.random.normal(key, (B, cfg.n_frames, cfg.d_model))
    full = ED.forward(params, cfg, toks, frames)
    memory = ED.encode(params, cfg, frames)
    cache = ED.init_cache(cfg, B, S)
    dec = jax.jit(lambda p, c, t, pos, m: ED.decode_step(p, cfg, t, c, pos, m))
    outs = []
    for i in range(S):
        lg, cache = dec(params, cache, toks[:, i:i + 1], jnp.int32(i), memory)
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), rtol=2e-2, atol=2e-2)


def test_local_attention_window_respected(key):
    """Tokens beyond the window must not influence the output."""
    cfg = LM.LMConfig(name="w", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=64, head_dim=16,
                      block_pattern=("local",), window=4)
    params = LM.init_lm(key, cfg)
    toks = jax.random.randint(key, (1, 12), 0, 64)
    base, _ = LM.forward(params, cfg, toks)
    # perturb a token > window positions before the last query
    toks2 = toks.at[0, 2].set((toks[0, 2] + 1) % 64)
    pert, _ = LM.forward(params, cfg, toks2)
    np.testing.assert_allclose(np.asarray(base[0, -1]),
                               np.asarray(pert[0, -1]), rtol=1e-4, atol=1e-4)


def test_moe_capacity_and_aux(key):
    spec = configs.get("kimi-k2-1t-a32b")
    cfg = spec.smoke
    params = LM.init_lm(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    logits, aux = LM.forward(params, cfg, toks)
    assert float(aux) > 0.0          # load-balance loss is active
    assert bool(jnp.isfinite(logits).all())


def test_full_configs_match_assignment():
    """Exact architecture numbers from the assignment table."""
    t = {a: configs.get(a).full for a in ARCHS}
    q = t["qwen1.5-32b"]
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.d_ff,
            q.vocab, q.qkv_bias) == (64, 5120, 40, 40, 27392, 152064, True)
    y6 = t["yi-6b"]
    assert (y6.n_layers, y6.d_model, y6.n_heads, y6.n_kv_heads, y6.d_ff,
            y6.vocab) == (32, 4096, 32, 4, 11008, 64000)
    y9 = t["yi-9b"]
    assert y9.n_layers == 48 and y9.d_ff == 11008
    g = t["gemma3-1b"]
    assert (g.n_layers, g.d_model, g.n_heads, g.n_kv_heads, g.d_ff,
            g.vocab) == (26, 1152, 4, 1, 6912, 262144)
    assert g.layer_types.count("attn") * 5 <= g.layer_types.count("local") + 5
    k = t["kimi-k2-1t-a32b"]
    assert (k.n_layers, k.d_model, k.n_heads, k.n_kv_heads, k.d_ff,
            k.vocab) == (61, 7168, 64, 8, 2048, 163840)
    assert k.moe.num_experts == 384 and k.moe.top_k == 8
    l4 = t["llama4-scout-17b-a16e"]
    assert (l4.n_layers, l4.d_model, l4.vocab) == (48, 5120, 202048)
    assert l4.moe.num_experts == 16 and l4.moe.top_k == 1
    x = t["xlstm-125m"]
    assert (x.n_layers, x.d_model, x.vocab, x.d_ff) == (12, 768, 50304, 0)
    w = t["whisper-tiny"]
    assert (w.d_model, w.n_heads, w.d_ff, w.vocab) == (384, 6, 1536, 51865)
    r = t["recurrentgemma-9b"]
    assert (r.n_layers, r.d_model, r.n_heads, r.d_ff, r.vocab) == (
        38, 4096, 16, 12288, 256000)
    i = t["internvl2-1b"]
    assert (i.n_layers, i.d_model, i.n_heads, i.n_kv_heads, i.d_ff,
            i.vocab) == (24, 896, 14, 2, 4864, 151655)


def test_param_count_kimi_is_about_1t():
    from repro.launch.specs import _param_counts
    total, active = _param_counts(configs.get("kimi-k2-1t-a32b").full)
    assert 0.7e12 < total < 1.4e12, f"kimi total {total/1e12:.2f}T"
    assert 20e9 < active < 50e9, f"kimi active {active/1e9:.1f}B"


# ---------------------------------------------------------------------------
# Chunked prefill: bit-exact vs the token-by-token decode walk
# ---------------------------------------------------------------------------
def _greedy_from(params, cfg, logits, cache, P, G):
    """G greedy tokens decoded from a prefill's last logits and cache."""
    decode_jit = jax.jit(lambda p, c, t, pos: LM.decode_step(p, cfg, t, c, pos))
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    out = []
    for j in range(G):
        out.append(np.asarray(tok)[:, 0])
        logits, cache = decode_jit(params, cache, tok, jnp.int32(P + j))
        tok = jnp.argmax(logits[:, -1:], axis=-1)
    return np.stack(out, axis=1)


def _tokenwise_prefill(params, cfg, toks, S_max):
    decode_jit = jax.jit(lambda p, c, t, pos: LM.decode_step(p, cfg, t, c, pos))
    cache = LM.init_cache(cfg, toks.shape[0], S_max)
    logits = []
    for i in range(toks.shape[1]):
        lg, cache = decode_jit(params, cache, toks[:, i:i + 1], jnp.int32(i))
        logits.append(np.asarray(lg))
    return np.concatenate(logits, axis=1), cache


@pytest.mark.parametrize("arch_id,P,block", [
    ("gemma3-1b", 12, 5),        # local+global attention, wide mode
    ("gemma3-1b", 20, 7),        # P > window 16: ring wrap -> scan mode
    ("recurrentgemma-9b", 12, 4),  # RG-LRU + local hybrid
    ("xlstm-125m", 12, 6),       # mLSTM/sLSTM states
    ("internvl2-1b", 8, 8),      # whole prompt, one wide block (qwen2-style)
    ("yi-6b", 8, 8),             # whole prompt, one wide block (llama-style)
])
def test_chunked_prefill_bit_exact(arch_id, P, block, key):
    """LM.prefill consumes the prompt in blocks yet must reproduce the
    decode path EXACTLY — logits and every cache leaf — in both the wide
    and the scan (ring-wrap / recurrent) modes."""
    cfg = configs.get(arch_id).smoke
    params = LM.init_lm(key, cfg)
    B, G = 2, 4
    S_max = P + G
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, P), 0, cfg.vocab)
    ref_logits, ref_cache = _tokenwise_prefill(params, cfg, toks, S_max)

    cache = LM.init_cache(cfg, B, S_max)
    wide = P <= LM._min_attn_cache(cfg, cache)
    assert wide == (not (arch_id == "gemma3-1b" and P == 20))
    logits, cache = LM.prefill(params, cfg, toks, cache, block=block,
                               last_only=False)
    np.testing.assert_array_equal(np.asarray(logits), ref_logits)
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(ref_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # last_only returns exactly the final position's logits
    cache2 = LM.init_cache(cfg, B, S_max)
    last, _ = LM.prefill(params, cfg, toks, cache2, block=block)
    np.testing.assert_array_equal(np.asarray(last), ref_logits[:, -1:])


@pytest.mark.parametrize("arch_id", ["internvl2-1b", "yi-6b"])
def test_whole_prompt_prefill_matches_tokenwise(arch_id, key):
    """The stream engine's admission shape: the whole prompt in ONE wide
    block.  Off the CPU's small-dot range (query-group rows G*C above 16
    there, here 2*24) XLA:CPU contracts the attention einsums with another
    kernel than the decode step's, so logits and caches agree to f32
    rounding, not bit for bit; the greedy continuation agrees exactly."""
    cfg = configs.get(arch_id).smoke
    params = LM.init_lm(key, cfg)
    B, P, G = 2, 24, 6
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, P), 0, cfg.vocab)
    ref_logits, ref_cache = _tokenwise_prefill(params, cfg, toks, P + G)
    cache = LM.init_cache(cfg, B, P + G)
    assert P <= LM._min_attn_cache(cfg, cache)
    logits, cache = LM.prefill(params, cfg, toks, cache, block=P,
                               last_only=False)
    np.testing.assert_allclose(np.asarray(logits), ref_logits,
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(ref_cache)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        _greedy_from(params, cfg, logits, cache, P, G),
        _greedy_from(params, cfg, jnp.asarray(ref_logits), ref_cache, P, G))


def test_chunked_prefill_then_decode_matches(key):
    """Greedy decode from a chunked prefill continues identically to one
    from the token-by-token prefill (the serving handoff point)."""
    cfg = configs.get("gemma3-1b").smoke
    params = LM.init_lm(key, cfg)
    B, P, G = 2, 10, 6
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, P), 0, cfg.vocab)
    lg_ref, cache_ref = _tokenwise_prefill(params, cfg, toks, P + G)
    gen_ref = _greedy_from(params, cfg, jnp.asarray(lg_ref[:, -1:]),
                           cache_ref, P, G)
    cache = LM.init_cache(cfg, B, P + G)
    lg, cache = LM.prefill(params, cfg, toks, cache, block=4)
    gen = _greedy_from(params, cfg, lg, cache, P, G)
    np.testing.assert_array_equal(gen, gen_ref)
