"""CPU accelerator implementation (reference parallel:
accelerator/cpu_accelerator.py). Used by the test suite's virtual 8-device
mesh and as the fallback when no TPU is attached."""

from __future__ import annotations

from typing import Any, Optional

import jax

from .abstract_accelerator import DeepSpeedAccelerator


class CPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self):
        super().__init__()
        self._name = "cpu"
        self.communication_backend = "xla"

    def _devices(self):
        return [d for d in jax.local_devices() if d.platform == "cpu"]

    def is_available(self) -> bool:
        return True

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return "cpu"
        return f"cpu:{device_index}"

    def device(self, device_index: Optional[int] = None) -> Any:
        return self._devices()[device_index or 0]

    def device_count(self) -> int:
        return len(self._devices())

    def global_device_count(self) -> int:
        return len([d for d in jax.devices() if d.platform == "cpu"])

    def synchronize(self, device_index: Optional[int] = None) -> None:
        pass

    def memory_stats(self, device_index: Optional[int] = None) -> dict:
        try:
            import resource
            peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            peak = 0
        try:
            import psutil
            vm = psutil.virtual_memory()
            return {"bytes_in_use": vm.used, "peak_bytes_in_use": peak,
                    "bytes_limit": vm.total}
        except Exception:
            return {"bytes_in_use": 0, "peak_bytes_in_use": peak,
                    "bytes_limit": 0}

    def peak_flops(self, dtype: Any = None, device_index: Optional[int] = None) -> float:
        # arbitrary floor with no measurement behind it (a CPU has no
        # peak this package measures against; ROADMAP.md queue 3 item 5)
        return 1e12

    def pin_memory(self, array, align_bytes: int = 1):
        return array  # host memory is host memory
