"""Serving example: batched requests against gemma3-1b (reduced config),
with a forget request applied IN PLACE between batches — no retraining,
no weight reload; the server keeps serving on the edited weights.

Serving drives unlearning through the ``repro.api.Unlearner`` facade with
one typed ``UnlearnSpec`` (echoed into the result for auditability), and
``--cache-dir`` keeps JAX's persistent compilation cache on disk, here in
its fixed home (``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's
``.jax_cache``): the second run below replays every compiled program
instead of recompiling.

``--fisher-refresh 1`` keeps the global importance I_D fresh: after every
drain edits the weights, retain microbatches are folded — at the now-edited
parameters — into an EMA of I_D (one compiled refresh program in the same
warm session), so later forget requests dampen against an importance map
that still describes the weights being served (DESIGN.md §10).

    PYTHONPATH=src python examples/serve_with_unlearning.py
"""
from repro.api import resolve_cache_dir
from repro.launch import serve

args = [
    "--arch", "gemma3-1b",
    "--requests", "4",
    "--prompt-len", "12",
    "--gen-len", "6",
    "--unlearn-after", "1",
    "--forget-domain", "1",
    "--cache-dir", resolve_cache_dir(),
    "--fisher-refresh", "1",
]
res = serve.main(args)
assert res["unlearned"]
print("served batches:", [r["latency_s"] for r in res["served"]])
print("unlearning stopped at layer:", res["unlearn_stats"]["stopped_at_l"])
print("unlearn spec:", res["unlearn_spec"])
refresh = res["fisher_refresh"]
assert refresh["refreshes"] >= 1
assert refresh["staleness"]["improved"]
print(f"fisher refresh: {refresh['refreshes']} refresh(es), I_D rel err "
      f"{refresh['staleness']['stale_rel_err']:.4f} -> "
      f"{refresh['staleness']['refreshed_rel_err']:.4f} vs a "
      "from-scratch recompute at the edited weights")
n_cached = res["compilation_cache"]["entries_new"]
print(f"compilation cache: {n_cached} new programs persisted to disk")

# serve again against the warm disk cache: within this process the
# already-initialized cache config keeps pointing at the same dir, so the
# --check gate verifies zero new entries were written
res2 = serve.main(args + ["--check"])
assert res2["compilation_cache"]["entries_new"] == 0
print("warm-cache rerun compiled nothing new")
