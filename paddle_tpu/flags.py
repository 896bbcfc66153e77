"""Global flag/config tree.

Reference analog: the gflags tier (platform/flags.cc ~40 FLAGS_*) surfaced by
``__bootstrap__`` (python/paddle/fluid/__init__.py:122 reads FLAGS_* env vars).

TPU-native: one typed dict; FLAGS_* env vars override at import; memory
fraction/allocator knobs are accepted but inert (XLA owns HBM)."""
from __future__ import annotations

import os
from typing import Any, Dict

_FLAGS: Dict[str, Any] = {
    # numeric guards (operator.cc:949 CheckTensorNANOrInf analog)
    "check_nan_inf": False,
    # matmul precision: 'default' (bf16 on MXU) | 'float32' | 'highest'
    "matmul_precision": "default",
    # async input pipeline: max un-synced Executor.run dispatches
    # train_from_dataset keeps in flight (1 = sync every step; 2 = classic
    # double buffering — host prepares N+1 while the device runs N)
    "max_inflight_steps": 2,
    # inert reference-compat knobs
    "fraction_of_gpu_memory_to_use": 0.92,
    "allocator_strategy": "auto_growth",
    "sync_nccl_allreduce": True,
    "selected_gpus": "",
    "eager_delete_tensor_gb": 0.0,
    "cudnn_deterministic": False,
}

_PRECISION_MAP = {"default": None, "float32": "float32", "highest": "highest",
                  "bfloat16": "bfloat16"}


def set_flags(flags: Dict[str, Any]):
    import jax
    for k, v in flags.items():
        key = k[6:] if k.startswith("FLAGS_") else k
        if key not in _FLAGS:
            raise KeyError(f"unknown flag {key!r}")
        if key == "matmul_precision":
            if v not in _PRECISION_MAP:
                raise ValueError(
                    f"FLAGS_matmul_precision={v!r}: must be one of "
                    f"{sorted(_PRECISION_MAP)}")
            jax.config.update("jax_default_matmul_precision", _PRECISION_MAP[v])
        _FLAGS[key] = v


def get_flags(keys):
    if isinstance(keys, str):
        keys = [keys]
    return {k: _FLAGS[k[6:] if k.startswith("FLAGS_") else k] for k in keys}


def flag(key: str):
    return _FLAGS[key]


# runtime knobs also honored under their PDTPU_* spelling (the env names
# documented alongside PDTPU_FUSE_UPDATES)
_ENV_ALIASES = {
    "PDTPU_MAX_INFLIGHT_STEPS": "max_inflight_steps",
}


def _coerce(key: str, v: str):
    cur = _FLAGS[key]
    if isinstance(cur, bool):
        return v.lower() in ("1", "true", "yes")
    if isinstance(cur, float):
        return float(v)
    if isinstance(cur, int):
        return int(v)
    return v


def _bootstrap_from_env():
    for k, v in os.environ.items():
        key = _ENV_ALIASES.get(k) if not k.startswith("FLAGS_") else k[6:]
        if key is None or key not in _FLAGS:
            continue
        _FLAGS[key] = _coerce(key, v)


_bootstrap_from_env()
