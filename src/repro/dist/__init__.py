"""Distributed runtime: sharding rules, compressed gradient sync, serving.

Modules:

- :mod:`repro.dist.compat`        — manual-region collective helpers
- :mod:`repro.dist.sharding`      — logical-axis annotations and logical→mesh rules
- :mod:`repro.dist.sharded_codec` — quantized reduce-scatter / ring-mean wire codecs
- :mod:`repro.dist.train_step`    — jitted shard_map train step (dsgd / two_phase /
  hierarchical / faithful sync, optional layer-streamed backward)
- :mod:`repro.dist.serve_step`    — sharded prefill + decode entry points
- :mod:`repro.dist.collectives`   — analytic per-device wire accounting

``train_step`` and ``serve_step`` import the model zoo (which itself uses
:func:`repro.dist.sharding.shard`), so they are exposed lazily to keep the
``models ⇄ dist`` import cycle one-directional at package-init time.
"""
from . import collectives, compat, sharded_codec, sharding
from .collectives import wire_bytes_per_device
from .sharding import shard

_LAZY = {
    "train_step": ("repro.dist.train_step", None),
    "serve_step": ("repro.dist.serve_step", None),
    "make_train_step": ("repro.dist.train_step", "make_train_step"),
    "TrainStepConfig": ("repro.dist.train_step", "TrainStepConfig"),
    "SYNC_MODES": ("repro.dist.train_step", "SYNC_MODES"),
    "make_serve_fns": ("repro.dist.serve_step", "make_serve_fns"),
}

__all__ = [
    "collectives",
    "compat",
    "shard",
    "sharded_codec",
    "sharding",
    "wire_bytes_per_device",
    *sorted(_LAZY),
]


def __getattr__(name: str):
    if name in _LAZY:
        import importlib

        module_name, attr = _LAZY[name]
        module = importlib.import_module(module_name)
        return module if attr is None else getattr(module, attr)
    raise AttributeError(f"module 'repro.dist' has no attribute {name!r}")
