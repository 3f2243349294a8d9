"""Pallas TPU kernels for the framework's perf-critical compute (DESIGN.md §6).

All kernels use explicit BlockSpec VMEM tiling and are validated against
pure-jnp oracles (ref.py) with ``interpret=True`` on CPU.  Every entry
point defaults to ``interpret=False`` (compile for the TPU): a caller that
wants the interpreter says so.  The FL round hot path (per-client
histograms + masked weighted aggregation) reaches these kernels through the trace-time backend
switch in ``dispatch.py`` — TPU compiles the Pallas kernels, CPU/GPU fall
back to the XLA references (the accumulator patterns are TPU-shaped; see
dispatch's docstring), and every engine routes through that one switch.

Lazy exports: the data/engine layers import ``repro.kernels.dispatch`` on
every process start, so this package __init__ must stay import-light — each
kernel family loads on first attribute access, not eagerly.  ``from
repro.kernels import flash_attention`` etc. keep working unchanged.  Two
export names (``flash_attention``, ``ssd_scan``) equal their subpackage's
name, and a deep import (``import repro.kernels.ssd_scan.ops``) makes the
import machinery bind the SUBPACKAGE as a package attribute; the module
class below resolves exported names through ``__getattribute__`` so the
exported callable always wins — matching the old eager ``__init__``, where
the from-import binding shadowed the subpackage.

``client_statistics`` resolves to the DISPATCH version (histogram + σ²/n
with ``backend=``); the raw always-Pallas wrapper remains importable as
``repro.kernels.label_hist.ops.client_statistics``.
"""
import importlib
import sys
import types

# public name -> (submodule, attribute)
_EXPORTS = {
    "weighted_agg_kernel": (".weighted_agg.weighted_agg", "weighted_agg_kernel"),
    "aggregate_params": (".weighted_agg.ops", "aggregate_params"),
    "normalized_scales": (".weighted_agg.ops", "normalized_scales"),
    "weighted_agg_ref": (".weighted_agg.ref", "weighted_agg_ref"),
    "label_hist_kernel": (".label_hist.label_hist", "label_hist_kernel"),
    "label_hist_ref": (".label_hist.ref", "label_hist_ref"),
    "flash_attention": (".flash_attention.flash_attention", "flash_attention"),
    "gqa_flash_attention": (".flash_attention.ops", "gqa_flash_attention"),
    "attention_ref": (".flash_attention.ref", "attention_ref"),
    "ssd_scan": (".ssd_scan.ssd_scan", "ssd_scan"),
    "ssd_apply": (".ssd_scan.ops", "ssd_apply"),
    "ssd_ref": (".ssd_scan.ref", "ssd_ref"),
    "client_histograms": (".dispatch", "client_histograms"),
    "client_statistics": (".dispatch", "client_statistics"),
    "compute_backend": (".dispatch", "compute_backend"),
    "masked_weighted_mean": (".dispatch", "masked_weighted_mean"),
    "weighted_sum_tree": (".dispatch", "weighted_sum_tree"),
}

__all__ = list(_EXPORTS)


class _LazyKernelsModule(types.ModuleType):
    """Resolves ``_EXPORTS`` names lazily and KEEPS them resolved: if the
    stored attribute is a module (the import machinery's subpackage binding,
    or nothing yet), the exported callable is imported and cached over it."""

    def __getattribute__(self, name):
        if name in _EXPORTS:
            d = object.__getattribute__(self, "__dict__")
            value = d.get(name)
            if value is None or isinstance(value, types.ModuleType):
                modname, attr = _EXPORTS[name]
                value = getattr(importlib.import_module(modname, __name__),
                                attr)
                d[name] = value      # cache; shadows any subpackage binding
            return value
        return object.__getattribute__(self, name)

    def __dir__(self):
        return sorted(set(object.__getattribute__(self, "__dict__"))
                      | set(_EXPORTS))


sys.modules[__name__].__class__ = _LazyKernelsModule
