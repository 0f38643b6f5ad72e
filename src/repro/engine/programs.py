"""Process-level compiled-program cache — shared across sessions/tenants.

``UnlearnSession`` historically owned its program dictionaries (fused,
checkpoint, refresh, sweep, fakequant families), which is the right scope
for ONE served model but the wrong scope for a multi-tenant fleet: N tenants
whose adapters share a layer-kind+shape signature would compile the same
executables N times and hold N copies live.  ``ProgramCache`` lifts those
dictionaries to an injectable object:

  * every session namespaces its keys by a FAMILY tuple
    ``(adapter.name, n_layers, donate)`` — tenants of the same model family
    (and donation regime) share entries, different families can never
    collide (their namespace differs even if some leaf shapes coincide);
  * within a namespace the keys are the sessions' existing signature keys
    (layer kind + shape signatures + static config), i.e. exactly the
    contract the per-session cache already enforced — lifting the dict does
    not change what counts as "the same program";
  * the cache counts ``compiles`` (builder ran) and ``hits`` process-wide,
    next to each session's per-tenant counters, so a fleet gate can assert
    "N same-family tenants compiled each program family exactly once" from
    one number.

A session built without an explicit cache gets a private ``ProgramCache``,
which reproduces the pre-fleet behavior bit-for-bit (single-tenant runs are
unchanged).  Sharing is sound because compiled programs close over only the
adapter's pure apply-closures: by the engine's ``layer_key`` contract, equal
kind + equal shapes within one family means the same function of
``(ctx, layer_p, act)``, so a program traced against tenant A's adapter
computes tenant B's request exactly — all tenant STATE (params, Fisher,
forget batches) enters as traced operands, never as captured constants.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Hashable, Tuple

from repro.obs import telemetry as _t

Builder = Callable[[], Callable]


def _key_fields(key: Hashable) -> Dict[str, str]:
    """Human-legible telemetry fields for a fully-qualified program key:
    the namespace (family) tuple and the session key's leading kind tag
    ("fused", "sweep", "refresh", ...)."""
    ns = fam = ""
    if isinstance(key, tuple) and key:
        ns = "/".join(map(str, key[0])) if isinstance(key[0], tuple) \
            else str(key[0])
        if len(key) > 1:
            sk = key[1]
            fam = str(sk[0]) if isinstance(sk, tuple) and sk else str(sk)
    return {"namespace": ns, "family": fam}


class ProgramCache:
    """Keyed store of compiled executables (and sweep plans) with process-
    wide compile/hit accounting.

    Keys are fully-qualified tuples ``(namespace,) + session_key``; the
    session is responsible for the namespace (its adapter family), this
    class is deliberately dumb about key structure.
    """

    def __init__(self):
        self._progs: Dict[Hashable, Callable] = {}
        self._plans: Dict[Hashable, Any] = {}
        self.compiles = 0   # a builder actually ran (traced + compiled)
        self.hits = 0       # an existing executable was replayed
        self.sessions = 0   # sessions attached (fleet reporting)

    # -- executables --------------------------------------------------------
    def get_or_build(self, key: Hashable, builder: Builder
                     ) -> Tuple[Callable, bool]:
        """Return ``(program, compiled)`` — ``compiled`` is True when the
        builder ran (a process-wide first for this key), False when any
        session (this tenant's or another's) already built it."""
        prog = self._progs.get(key)
        if prog is None:
            t0 = _t.wall_time()
            prog = builder()
            self._progs[key] = prog
            self.compiles += 1
            _t.emit("program.compile", compiles=self.compiles,
                    wall_s=round(_t.wall_time() - t0, 3),
                    **_key_fields(key))
            return prog, True
        self.hits += 1
        _t.emit("program.hit", hits=self.hits, **_key_fields(key))
        return prog, False

    def evict_where(self, pred: Callable[[Hashable], bool]) -> int:
        """Drop every executable whose key satisfies ``pred``; returns the
        number evicted (the refresh-family lifecycle: a re-armed stream's
        dead programs must not accumulate in a long-lived cache)."""
        dead = [k for k in self._progs if pred(k)]
        for k in dead:
            del self._progs[k]
        return len(dead)

    def keys(self):
        return self._progs.keys()

    def __len__(self) -> int:
        return len(self._progs)

    # -- shape-derived memos (pure structure, no compile counters) -----------
    def plan_or_build(self, key: Hashable, builder: Callable[[], Any]) -> Any:
        """Memo of what the session derives from shapes alone: sweep plans
        (``plan_scanned_sweep`` results, including the ``None`` =
        not-scannable verdict) and per-layer parameter counts.  Both come
        from ``jax.eval_shape`` so they carry no compile cost worth
        counting here, but same-family tenants still skip re-deriving them."""
        if key not in self._plans:
            self._plans[key] = builder()
        return self._plans[key]

    # -- reporting ----------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        return {"programs": len(self._progs), "compiles": self.compiles,
                "hits": self.hits, "sessions": self.sessions}
