"""``Unlearner`` — the one facade every unlearning call site drives.

Owns the three long-lived pieces of the FiCABU service:

  * the ``ModelAdapter`` (the per-layer view of the served model),
  * the global Fisher importance I_D and its lifecycle (computed once per
    served model, structure-locked thereafter — a refresh with a
    structurally different tree is a ``ValueError``, never a silent clobber),
  * ONE warm ``repro.engine.UnlearnSession`` whose compiled-program cache
    persists across every forget request and coalesced drain.

The same object drives serve.py drains, the pod-mesh dry-run
(``shard(mesh)``: parameters/batches/Fisher laid out by
``ExecSpec.param_pspecs``/``batch_pspec``, fused steps donating layer
buffers), benchmarks and the examples.  Requests are ``ForgetRequest``s (or
bare ``(inputs, labels)`` pairs); configuration is an ``UnlearnSpec``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.cau import ModelAdapter, UnlearnConfig
from repro.obs import telemetry as _t
from repro.engine import (FisherStream, ProgramCache, RefreshPolicy,
                          UnlearnSession, shape_signature)

from .specs import RefreshSpec, UnlearnSpec

Params = Any


@dataclasses.dataclass(frozen=True)
class ForgetRequest:
    """One forget set: the model inputs and the labels whose mapping must be
    destroyed.  ``tag`` is free-form audit metadata (domain id, ticket id)
    carried into the returned stats."""
    inputs: Any
    labels: Any
    tag: Optional[Any] = None


CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout's own cache directory (gitignored): a fixed path, so every
# process started from this checkout finds what earlier ones compiled
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """Where the persistent compilation cache lives: the environment's
    ``JAX_COMPILATION_CACHE_DIR`` when it is set (no argument overrides
    it), else ``cache_dir``, else the checkout's ``.jax_cache``."""
    return os.environ.get(CACHE_ENV) or cache_dir or DEFAULT_CACHE_DIR


def enable_compilation_cache(cache_dir: Optional[str] = None) -> int:
    """Point JAX's persistent compilation cache at ``resolve_cache_dir(
    cache_dir)`` (created if missing) with thresholds dropped to zero so
    every program is eligible.
    Returns the number of entries already on disk — a cold process start
    with a warm cache should then add ZERO new entries (the serve.py
    ``--check`` gate asserts exactly that).  Idempotent for the same dir;
    the cache is PROCESS-GLOBAL, so pointing it somewhere else after it was
    configured raises instead of silently repointing every facade's cache
    (per-tenant cache dirs are the ROADMAP multi-tenant item, not this)."""
    env_dir = os.environ.get(CACHE_ENV)
    cache_dir = resolve_cache_dir(cache_dir)
    current = jax.config.jax_compilation_cache_dir
    if not env_dir and current \
            and os.path.abspath(current) != os.path.abspath(cache_dir):
        raise ValueError(
            f"the persistent compilation cache already points at {current!r} "
            f"for this process; refusing to repoint it to {cache_dir!r} — "
            "JAX's cache dir is process-global, so concurrent facades would "
            "intermix entries and corrupt each other's cold-start accounting")
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return compilation_cache_entries(cache_dir)


def compilation_cache_entries(cache_dir: str) -> int:
    """Number of serialized executables currently in ``cache_dir``."""
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if not n.startswith("."))
    except FileNotFoundError:
        return 0


def _coerce_request(req) -> ForgetRequest:
    if isinstance(req, ForgetRequest):
        return req
    if isinstance(req, (tuple, list)) and len(req) == 2:
        return ForgetRequest(inputs=req[0], labels=req[1])
    raise ValueError(
        "a forget request must be a ForgetRequest or an (inputs, labels) "
        f"pair, got {type(req).__name__}")


class Unlearner:
    """The unlearning service facade: ``forget`` / ``forget_group`` /
    ``shard``, all configured by one ``UnlearnSpec``.

    >>> unl = Unlearner(adapter, fisher_global, UnlearnSpec.for_mode("ficabu"))
    >>> params, stats = unl.forget(ForgetRequest(fx, fy), params=params)

    ``session=`` adopts an existing warm ``UnlearnSession`` (its compiled
    programs survive); otherwise the facade builds one lazily on the first
    request.  A Fisher tree whose structure differs from the session's is
    rejected — refresh values, never shape (the engine's cached programs are
    specialized to the Fisher leaf shapes).

    ``programs=`` injects a process-level ``repro.engine.ProgramCache`` into
    the facade's session — the multi-tenant fleet hands every tenant the
    same cache so same-family tenants compile each program family once.
    ``name=`` labels this facade (the fleet's tenant name) in diagnostics
    and error messages; it defaults to the adapter's model name.
    """

    def __init__(self, adapter: ModelAdapter,
                 fisher_global: Optional[Params] = None,
                 spec: Optional[UnlearnSpec] = None, *,
                 session: Optional[UnlearnSession] = None,
                 programs: Optional[ProgramCache] = None,
                 name: Optional[str] = None):
        if not isinstance(adapter, ModelAdapter):
            raise ValueError(
                f"Unlearner needs a repro.core.ModelAdapter (see "
                f"repro.core.adapters), got {type(adapter).__name__}")
        spec = UnlearnSpec() if spec is None else spec
        if not isinstance(spec, UnlearnSpec):
            raise ValueError(
                f"spec must be an UnlearnSpec (see repro.api), "
                f"got {type(spec).__name__}")
        if programs is not None and not isinstance(programs, ProgramCache):
            raise ValueError(
                f"programs must be a repro.engine.ProgramCache (the "
                f"process-level compiled-program store a fleet shares "
                f"across tenants), got {type(programs).__name__}")
        self.adapter = adapter
        self.spec = spec
        self.name: str = adapter.name if name is None else str(name)
        self._programs = programs
        self.mesh = None
        self._fisher: Optional[Params] = None
        self._session: Optional[UnlearnSession] = None
        # streamed-Fisher refresh state (enable_fisher_refresh)
        self._stream: Optional[FisherStream] = None
        self._refresh_policy: Optional[RefreshPolicy] = None
        self._refresh_batches: List[Any] = []
        self._refresh_cursor = 0
        self._drains_since_refresh = 0
        self._edited_since_refresh = 0
        self._param_count = 0
        self.refresh_log: List[Dict] = []
        if session is not None:
            if session.adapter is not adapter:
                raise ValueError(
                    "the supplied UnlearnSession is bound to adapter "
                    f"{session.adapter.name!r}, not {adapter.name!r}; a warm "
                    "session's compiled programs are adapter-specific — "
                    "build a new Unlearner for the other model")
            if programs is not None and session.programs is not programs:
                raise ValueError(
                    "session= and programs= disagree: the supplied warm "
                    "session already holds its own program cache — adopt "
                    "the session without programs=, or build a fresh "
                    "Unlearner around the shared cache")
            self._session = session
            self._fisher = session.fisher_global
        if fisher_global is not None:
            self.set_fisher(fisher_global)
        if spec.exec.cache_dir is not None:
            enable_compilation_cache(spec.exec.cache_dir)

    def _owner_desc(self) -> str:
        """Who this facade's Fisher/session belong to, for error messages:
        the tenant name when the facade is fleet-labelled, always the
        model."""
        if self.name != self.adapter.name:
            return f"tenant {self.name!r} (model {self.adapter.name!r})"
        return f"model {self.adapter.name!r}"

    # -- Fisher lifecycle ---------------------------------------------------
    @property
    def fisher_global(self) -> Optional[Params]:
        return self._fisher

    def set_fisher(self, tree: Params) -> "Unlearner":
        """Install / refresh the global Fisher importance I_D.

        Values may be refreshed at any time (the streamed-refresh path);
        STRUCTURE may not: a tree whose treedef / leaf shapes / dtypes
        differ from the one the warm session compiled against raises
        ``ValueError`` instead of silently clobbering the session state
        (the old ``ficabu.unlearn_group`` bug)."""
        if tree is None:
            raise ValueError("set_fisher needs a Fisher pytree; to compute "
                             "one, use ensure_fisher(loss_fn, params, batch)")
        anchor = self._fisher
        if anchor is not None \
                and shape_signature(tree) != shape_signature(anchor):
            # name WHO this Fisher was armed for: with N pooled tenants a
            # bare shape dump is ambiguous — the usual cause is handing
            # tenant A's facade a tree computed for tenant B's model
            raise ValueError(
                f"refusing to replace the global Fisher armed for "
                f"{self._owner_desc()} with a structurally different tree "
                "(treedef/leaf shapes/dtypes changed) — the warm session's "
                "compiled programs are specialized to the current "
                "structure, and a mismatched tree usually means this is "
                "another tenant's/model's Fisher. Refresh Fisher VALUES "
                "with the same structure, or build a new Unlearner for "
                "the new model.")
        if self.mesh is not None:
            tree = self.place_params(tree)  # same layout rule as params
        self._fisher = tree
        if self._session is not None:
            self._session.fisher_global = tree
        if self._stream is not None:
            # keep the EMA state coherent with MANUAL value refreshes too:
            # the next streamed fold must start from the installed tree,
            # not silently revert to a pre-update total
            self._stream.total = tree
        return self

    def ensure_fisher(self, loss_fn, params: Params, batch,
                      chunk_size: Optional[int] = None) -> Params:
        """Compute the global Fisher ONCE (diagonal, over ``batch``) if this
        facade does not hold one yet; later calls are no-ops returning the
        stored tree (the once-per-served-model lifecycle)."""
        if self._fisher is None:
            from repro.core import fisher as fisher_mod
            cs = self.spec.exec.chunk_size if chunk_size is None else chunk_size
            self.set_fisher(fisher_mod.diag_fisher(loss_fn, params, batch,
                                                   chunk_size=cs))
        return self._fisher

    # -- streamed Fisher refresh (DESIGN.md §10) ----------------------------
    @property
    def fisher_stream(self) -> Optional[FisherStream]:
        """The streamed-refresh maintainer (None until
        ``enable_fisher_refresh``)."""
        return self._stream

    def enable_fisher_refresh(self, policy, batches: Sequence,
                              loss_fn, *, chunk_size: Optional[int] = None
                              ) -> "Unlearner":
        """Arm the streamed global-Fisher refresh: between drains, fold
        retain microbatches (evaluated at the CURRENT, post-edit weights)
        into an EMA of I_D and install the result through the
        structure-locked ``set_fisher``.

        ``policy`` is a ``RefreshSpec``/``RefreshPolicy`` (or None to take
        ``spec.refresh``); ``batches`` the retain microbatches the refresh
        cycles through; ``loss_fn(params, batch) -> scalar`` the same
        mean-NLL the one-shot Fisher used.  The compiled refresh step lives
        in the warm session's program cache next to the fused families, so
        the zero-retrace lifecycle covers it (``session.stats``
        refresh_compiles/refresh_hits).  The serving loop then calls
        ``refresh_if_due(params)`` after every drain."""
        if policy is None:
            policy = self.spec.refresh
        if isinstance(policy, RefreshSpec):
            policy = policy.to_policy()
        if not isinstance(policy, RefreshPolicy):
            raise ValueError(
                "enable_fisher_refresh needs a RefreshSpec/RefreshPolicy "
                "(or spec.refresh set when passing None), got "
                f"{type(policy).__name__}")
        if self._fisher is None:
            raise ValueError(
                "no global Fisher importance installed to refresh — call "
                "ensure_fisher(loss_fn, params, batch) or set_fisher(tree) "
                "before enable_fisher_refresh")
        batches = list(batches)
        if not batches:
            raise ValueError(
                "enable_fisher_refresh needs at least one retain microbatch "
                "to fold (an empty refresh would silently keep I_D stale)")
        for i, b in enumerate(batches):
            leaves = jax.tree_util.tree_leaves(b)
            if not leaves or int(leaves[0].shape[0]) < 1:
                raise ValueError(
                    f"refresh microbatch {i} has no samples (leading "
                    f"dimension is 0) — an upstream slice exhausted it; a "
                    f"zero-sample Fisher would be all-NaN and poison I_D")
        cs = self.spec.exec.chunk_size if chunk_size is None else chunk_size
        sess = self._ensure_session()
        if self._stream is not None:
            # re-arming (new loss_fn/policy/batches): the dead stream's
            # compiled programs must not linger in the session cache — and
            # must never be replayed for the new stream (its cache_token
            # differs, so collisions are impossible by construction)
            sess.evict_refresh_programs(self._stream.cache_token)
        # same coercion as _ensure_session: the FACADE's donate=None means
        # NO donation (in-place consumption is strictly opt-in), even
        # though the engine-level default would auto-donate on accelerators
        self._stream = FisherStream(
            loss_fn, self._fisher, decay=policy.decay, chunk_size=cs,
            donate=bool(self.spec.exec.donate), programs=sess)
        self._refresh_policy = policy
        self._refresh_batches = batches
        self._refresh_cursor = 0
        self._drains_since_refresh = 0
        self._edited_since_refresh = 0
        self._param_count = sum(
            int(x.size) for x in jax.tree_util.tree_leaves(self._fisher))
        return self

    def _note_drain(self, stats_list: Sequence[Dict]) -> None:
        """Account one drain toward the refresh policy triggers."""
        if self._stream is None:
            return
        self._drains_since_refresh += 1
        for st in stats_list:
            self._edited_since_refresh += sum(
                int(n) for n in st.get("selected_per_layer", {}).values())

    @property
    def edited_fraction(self) -> float:
        """Fraction of parameters edited since the last refresh (the
        staleness-trigger input)."""
        if not self._param_count:
            return 0.0
        return min(1.0, self._edited_since_refresh / self._param_count)

    def refresh_if_due(self, params: Params) -> Optional[Dict]:
        """Run a refresh when the policy says so; the serving loop calls
        this between drains.  Returns the refresh accounting entry, or None
        when nothing was due (or refresh is not enabled)."""
        if self._stream is None or self._refresh_policy is None:
            return None
        if not self._refresh_policy.due(self._drains_since_refresh,
                                        self.edited_fraction):
            return None
        return self.refresh_now(params)

    def refresh_now(self, params: Params,
                    max_batches: Optional[int] = None) -> Dict:
        """Fold up to ``max_batches`` retain microbatches (policy budget by
        default) at the CURRENT weights — equal-weighted within the refresh
        — into the EMA and install it through the structure-locked
        ``set_fisher``.  The stream state only moves after ``set_fisher``
        accepted the tree — a rejected refresh leaves both I_D and the EMA
        untouched."""
        if self._stream is None:
            raise ValueError("streamed refresh is not enabled — call "
                             "enable_fisher_refresh(policy, batches, "
                             "loss_fn) first")
        k = (self._refresh_policy.max_batches if max_batches is None
             else int(max_batches))
        if k < 1:
            raise ValueError(f"refresh_now max_batches must be >= 1, "
                             f"got {max_batches!r}")
        sess = self._ensure_session()
        comp0, hits0 = (sess.stats["refresh_compiles"],
                        sess.stats["refresh_hits"])
        if self.mesh is not None:
            params = self.place_params(params)
        # the budgeted microbatches enter with EQUAL weight: fold them into
        # a running mean (per-fold decay i/(i+1); the first fold's decay=0
        # discards the seed, which is only there to feed the program — a
        # protected COPY of the installed tree, so a donating step never
        # consumes the live I_D and a refresh failing mid-way cannot
        # invalidate it), then apply the policy decay ONCE per refresh
        # against the INSTALLED tree (manual set_fisher refreshes included)
        fresh_mean = self._stream.protect_live_input(self._fisher)
        folded = 0
        for _ in range(k):
            batch = self._refresh_batches[
                self._refresh_cursor % len(self._refresh_batches)]
            self._refresh_cursor += 1
            batch = self.place_batch(batch)
            fresh_mean = self._stream.fold_into(
                fresh_mean, params, batch, decay=folded / (folded + 1))
            folded += 1
        new_total = self._stream.blend(self._fisher, fresh_mean)
        self.set_fisher(new_total)      # structure-locked; may raise
        self._stream.commit(self._fisher, folded)
        # staleness at the refresh DECISION — captured before the trigger
        # counters reset, or telemetry would always report a fresh state
        drains_stale = self._drains_since_refresh
        edited_stale = self.edited_fraction
        self._drains_since_refresh = 0
        self._edited_since_refresh = 0
        entry = {
            "batches": folded,
            "ema_count": self._stream.count,
            "decay": self._stream.decay,
            "engine": {
                "refresh_compiles": sess.stats["refresh_compiles"] - comp0,
                "refresh_hits": sess.stats["refresh_hits"] - hits0,
            },
        }
        self.refresh_log.append(entry)
        _t.emit("fisher.refresh", name=self.name, batches=folded,
                ema_count=self._stream.count,
                drains_since_refresh=drains_stale,
                edited_fraction=round(edited_stale, 6),
                compiles=entry["engine"]["refresh_compiles"],
                hits=entry["engine"]["refresh_hits"])
        return entry

    # -- session ------------------------------------------------------------
    @property
    def session(self) -> Optional[UnlearnSession]:
        """The warm engine session (None until the first request)."""
        return self._session

    @property
    def stats(self) -> Dict[str, int]:
        """Engine program-cache counters (empty dict before the first
        request)."""
        return dict(self._session.stats) if self._session else {}

    def _ensure_session(self) -> UnlearnSession:
        if self._session is None:
            if self._fisher is None:
                raise ValueError(
                    "no global Fisher importance installed — pass "
                    "fisher_global to Unlearner(...), call set_fisher(tree), "
                    "or ensure_fisher(loss_fn, params, batch) first")
            # coerce explicitly: the ENGINE maps donate=None to auto-donate
            # on accelerators, but the facade's None means NO donation —
            # migrated call sites routinely reuse the pre-edit tree, so
            # in-place editing is strictly opt-in (ExecSpec.donate=True)
            donate = bool(self.spec.exec.donate)
            self._session = UnlearnSession(self.adapter, self._fisher,
                                           donate=donate,
                                           programs=self._programs)
            # fault-injection scoping: tenant-named facades key chaos
            # FaultSpecs by tenant, not by adapter family
            self._session.fault_scope = self.name
        # the scanned-sweep program lays its stacked [L, ...] trees out by
        # dist.sharding rules; hand the session the mesh + layout mode
        if self.mesh is not None:
            self._session.mesh = self.mesh
            self._session.mesh_sharding = self.spec.exec.sharding
        return self._session

    def with_spec(self, spec: UnlearnSpec) -> "Unlearner":
        """A sibling facade over the SAME adapter, Fisher, warm session and
        mesh, with a different request configuration — e.g. one deployment
        running "ssd" (baseline) and "ficabu" requests against one
        compiled-program cache.  The session is materialized here (if a
        Fisher is installed) so both facades share its warmth; the session's
        ``donate`` setting stays as first configured.  The streamed-refresh
        stream is NOT shared — exactly one facade should own the I_D
        write path (arm the sibling with enable_fisher_refresh if it is
        the one driving drains)."""
        sess = self._session
        if sess is None and self._fisher is not None:
            sess = self._ensure_session()
        sib = Unlearner(self.adapter, self._fisher, spec, session=sess,
                        programs=None if sess is not None else self._programs,
                        name=self.name)
        if self.mesh is not None:
            sib.shard(self.mesh)
        return sib

    # -- mesh execution -----------------------------------------------------
    def shard(self, mesh) -> "Unlearner":
        """Bind a device mesh: from here on every request's parameters and
        forget batches are laid out by ``ExecSpec.param_pspecs`` /
        ``batch_pspec`` before the sweep, and the stored Fisher is placed
        immediately.  Call before the first request — re-placing inputs
        after programs compiled would retrace them."""
        if mesh is None:
            raise ValueError("shard(mesh) needs a jax Mesh; to drop mesh "
                             "placement build a new Unlearner")
        axes = self.spec.exec.mesh_axes
        if axes is not None:
            missing = [a for a in axes if a not in mesh.shape]
            if missing:
                raise ValueError(
                    f"ExecSpec.mesh_axes {axes} not all present on the mesh "
                    f"(axes {tuple(mesh.shape)}): missing {missing}")
        self.mesh = mesh
        if self._session is not None:
            self._session.mesh = mesh
            self._session.mesh_sharding = self.spec.exec.sharding
        if self._fisher is not None:
            self.set_fisher(self._fisher)  # re-place on the new mesh
        return self

    def _named(self, pspec):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, pspec)

    def place_params(self, params: Params) -> Params:
        """device_put a parameter tree with this facade's layout rule
        (no-op without a mesh)."""
        if self.mesh is None:
            return params
        specs = self.spec.exec.param_pspecs(params, self.mesh)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, self._named(s)), params, specs)

    def place_batch(self, batch):
        """device_put a [B, ...] batch pytree with the DP layout (no-op
        without a mesh)."""
        if self.mesh is None:
            return batch

        def one(x):
            x = jax.numpy.asarray(x)
            ps = self.spec.exec.batch_pspec(self.mesh, int(x.shape[0]),
                                            x.ndim)
            return jax.device_put(x, self._named(ps))

        return jax.tree_util.tree_map(one, batch)

    # -- the API ------------------------------------------------------------
    def forget(self, request, *, params: Params,
               cfg: Optional[UnlearnConfig] = None
               ) -> Tuple[Params, Dict]:
        """Serve one forget request through the warm engine.  Returns
        ``(params', stats)``; ``cfg`` overrides the spec-derived engine
        config (legacy-shim path — normal callers configure via the spec)."""
        req = _coerce_request(request)
        sess = self._ensure_session()
        cfg = self.spec.to_config() if cfg is None else cfg
        if self.mesh is not None:
            params = self.place_params(params)
        inputs, labels = self.place_batch((req.inputs, req.labels))
        new_params, stats = sess.forget(params, inputs, labels, cfg)
        stats["mode"] = self.spec.mode
        if req.tag is not None:
            stats["tag"] = req.tag
        self._note_drain([stats])
        return new_params, stats

    def forget_group(self, requests: Sequence, *, params: Params,
                     reference: Optional[Params] = None,
                     cfg: Optional[UnlearnConfig] = None
                     ) -> Tuple[Params, List[Dict], Dict]:
        """Serve a GROUP of forget requests as ONE coalesced back-end-first
        sweep (a serving drain).  Returns ``(params', [stats per request],
        group_stats)``; per-request halting/MAC accounting is preserved."""
        reqs = [_coerce_request(r) for r in requests]
        if not reqs:
            raise ValueError("forget_group needs at least one forget "
                             "request; an empty drain should be skipped by "
                             "the caller")
        sess = self._ensure_session()
        cfg = self.spec.to_config() if cfg is None else cfg
        if self.mesh is not None:
            params = self.place_params(params)
            if reference is not None:
                reference = self.place_params(reference)
        sets = [self.place_batch((r.inputs, r.labels)) for r in reqs]
        new_params, stats_k, group_stats = sess.forget_many(
            params, sets, cfg, reference=reference)
        for r, st in zip(reqs, stats_k):
            st["mode"] = self.spec.mode
            if r.tag is not None:
                st["tag"] = r.tag
        group_stats["mode"] = self.spec.mode
        self._note_drain(stats_k)
        return new_params, stats_k, group_stats
