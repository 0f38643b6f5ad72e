"""Public unlearning API: typed specs + the ``Unlearner`` facade.

    from repro.api import Unlearner, UnlearnSpec, ForgetRequest

    spec = UnlearnSpec.for_mode("ficabu", alpha=10.0, tau=0.2)
    unl = Unlearner(adapter, fisher_global, spec)
    params, stats = unl.forget(ForgetRequest(fx, fy), params=params)

See DESIGN.md §9.  The legacy kwarg entry points (``repro.core.ficabu``)
are deprecation shims over this module and remain bit-identical.
"""
from .facade import (ForgetRequest, Unlearner,  # noqa: F401
                     compilation_cache_entries, enable_compilation_cache,
                     resolve_cache_dir)
from .specs import (MODES, DampenSpec, ExecSpec, HaltSpec,  # noqa: F401
                    QuantSpec, RefreshSpec, ServeSpec, UnlearnSpec)
