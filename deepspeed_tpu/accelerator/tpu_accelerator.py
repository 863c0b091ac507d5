"""TPU accelerator implementation (reference parallel:
accelerator/cuda_accelerator.py — the "real device" backend)."""

from __future__ import annotations

from typing import Any, Optional

import jax

from .abstract_accelerator import DeepSpeedAccelerator

# Peak dense bf16 FLOPS per chip by device-kind prefix. Sources: public TPU
# spec sheets (the benchmark keeps its own table, benchmark/lib/peaks.py).
_PEAK_FLOPS_BF16 = (
    ("TPU v6 lite", 918e12),   # Trillium
    ("TPU v5 lite", 197e12),   # v5e
    ("TPU v5", 459e12),        # v5p
    ("TPU v4 lite", 138e12),
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
    ("TPU v2", 45e12),
)


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self):
        super().__init__()
        self._name = "tpu"
        # Collectives are XLA-emitted over ICI/DCN; there is no NCCL-style
        # user-visible backend. The name is informational (comm facade).
        self.communication_backend = "xla"

    def _devices(self):
        return [d for d in jax.local_devices() if d.platform == "tpu"]

    def is_available(self) -> bool:
        # a backend that fails to initialise raises here: on a TPU host
        # that is a fault to surface, not "no TPU, carry on on the CPU"
        return len(self._devices()) > 0

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device(self, device_index: Optional[int] = None) -> Any:
        return self._devices()[device_index or 0]

    def device_count(self) -> int:
        return len(self._devices())

    def global_device_count(self) -> int:
        return len([d for d in jax.devices() if d.platform == "tpu"])

    def synchronize(self, device_index: Optional[int] = None) -> None:
        # Drain the async dispatch queue on every local device. This IS
        # the synchronization primitive: the per-device sync is its
        # contract, not an accident.
        for d in self._devices():
            try:
                jax.block_until_ready(   # graftlint: disable=GL003
                    jax.device_put(0, d))
            except Exception:
                pass

    def memory_stats(self, device_index: Optional[int] = None) -> dict:
        try:
            return dict(self.device(device_index).memory_stats() or {})
        except Exception:
            return {}

    def peak_flops(self, dtype: Any = None, device_index: Optional[int] = None) -> float:
        kind = getattr(self.device(device_index), "device_kind", "")
        for prefix, flops in _PEAK_FLOPS_BF16:
            if kind.startswith(prefix):
                import jax.numpy as jnp
                if dtype == jnp.float32:
                    return flops / 2  # MXU fp32 runs at half bf16 rate
                return flops
        raise ValueError(
            f"no published peak FLOP/s for device_kind {kind!r}; add it "
            f"to _PEAK_FLOPS_BF16 with its source (a utilization against "
            f"an assumed peak is not a measurement)")
