"""numsan — runtime numerics sanitizer (ISSUE 18 tentpole part 2).

The static rules (:mod:`.rules.numerics`, GL070-GL073) check what the
*source* says about accumulation/guard/rounding discipline; this module
checks what the *numbers actually did*. Until now the only runtime
numerics signal was one anonymous overflow bit
(``runtime/loss_scaler.py``): a blown-up step told you nothing about
which executable produced it, which PyTree leaf went non-finite, or
whether a quantized path was silently clipping long before the
overflow. :class:`NumericsSanitizer` promotes those forensics to named
findings:

- **nonfinite-grads**: the engine's train step folds per-leaf
  non-finite counts + max|g| into the same fused reduction that already
  computes the overflow bit (the engine's ``update`` half); a bad step raises/warns
  with the executable's ledger name (``compiled_step``) and the worst
  leaf's PyTree path — "which executable, which leaf, what kind of
  blow-up" instead of one bit.
- **nonfinite-logits / logits-range**: opt-in inference v2 dispatch
  probe — non-finite logits, or |logits| beyond a configured limit
  (the pre-NaN saturation signature of a mis-scaled KV cache).
- **nonfinite-kv-scale**: opt-in probe over the quantized KV pools'
  scale slabs.
- **saturation**: every quantize site (KV write, qgZ wire, MoE
  dispatch) reports its saturating-code fraction through
  :func:`report_saturation` (a trace-time-armed ``jax.debug.callback``
  at the site — see ``ops/pallas/quantization.saturation_probe``);
  the fraction lands on the ``ds_numsan_saturation_ratio{site}``
  gauge and a fraction above the configured ceiling is a finding —
  silent clipping becomes a named, site-labelled signal.

Findings raise (:class:`NumSanError`) or warn per ``mode`` and bump
``ds_numsan_violations_total{kind}`` through the zero-import telemetry
probe. Findings born inside ``jax.debug.callback`` (the saturation
probes) cannot raise usefully from the runtime's callback thread, so
they are DEFERRED: the callback records them and the next host
choke-point calls :meth:`drain` (engine ``train_batch``, the v2
dispatch path, the seeded-fault tests) which raises the first pending
finding in raise mode.

Like blocksan/meshsan this module is host-only and stdlib-only — the
probes that ride executables live at the call sites (engine,
``ops/pallas/quantization.py``), keyed off :func:`get_numsan` through
a ``sys.modules`` lookup so nothing here is imported while the config
block and ``DS_NUMSAN`` are off; the disabled path stays
byte-identical.
"""

from __future__ import annotations

import os
import threading
from typing import Iterable, Optional

from .blocksan import _count_violation

_LOG_CAP = 64


class NumSanError(RuntimeError):
    """A numerics contract was violated (non-finite values or
    saturation beyond the configured ceiling)."""


def _set_gauge(metric: str, help_: str, value: float, **labels) -> None:
    """Best-effort gauge through the zero-import telemetry probe."""
    try:
        from ..utils.telemetry_probe import active_telemetry
        tel = active_telemetry()
        reg = tel.get_registry() if tel is not None else None
        if reg is not None:
            reg.gauge(metric, help_).set(value, **labels)
    except Exception:
        pass


class NumericsSanitizer:
    """Named numerics findings with per-executable / per-leaf / per-site
    attribution. ``mode`` is raise|warn, mirroring the other
    sanitizers."""

    def __init__(self, mode: str = "raise",
                 saturation_ceiling: float = 0.05,
                 logits_limit: float = 1e4,
                 probe_interval: int = 16,
                 saturation_probe: bool = True):
        if mode not in ("raise", "warn"):
            raise ValueError(
                f"numsan mode must be raise|warn, got {mode!r}")
        self.mode = mode
        self.saturation_ceiling = float(saturation_ceiling)
        self.logits_limit = float(logits_limit)
        self.probe_interval = max(1, int(probe_interval))
        # armed at trace time by ops/pallas/quantization.saturation_probe
        self.saturation_probe = bool(saturation_probe)
        self._lock = threading.Lock()
        self.counters = {"checked_steps": 0, "saturation_reports": 0,
                         "violations": 0}
        self.violation_log: list[str] = []
        self.last_saturation: dict[str, float] = {}
        self.max_saturation: dict[str, float] = {}
        self._pending: list[str] = []

    # -- gradient attribution (engine train step) ----------------------
    def check_grad_stats(self, executable: str,
                         leaf_stats: Iterable[tuple],
                         loss_scale: Optional[float] = None) -> list[str]:
        """Check one step's per-leaf gradient stats. ``leaf_stats`` is
        an iterable of ``(path, nonfinite_count, max_abs)`` host
        numbers in PyTree-leaf order (the engine pairs the fused
        reduction's vectors with ``tree_leaves_with_path``). Returns
        finding messages; raises in raise mode."""
        with self._lock:
            self.counters["checked_steps"] += 1
        stats = [(str(p), int(n), float(m)) for p, n, m in leaf_stats]
        bad = [s for s in stats if s[1] > 0]
        if not bad:
            return []
        total = sum(s[1] for s in bad)
        worst = max(bad, key=lambda s: (s[1], s[2]))
        scale = (f", loss_scale={loss_scale:g}"
                 if loss_scale is not None else "")
        return [self._fail(
            f"executable '{executable}': {total} non-finite gradient "
            f"element(s) across {len(bad)}/{len(stats)} leaves — worst "
            f"leaf '{worst[0]}' ({worst[1]} non-finite, "
            f"max|g|={worst[2]:.3e}{scale}); the overflow bit now has "
            "a name: chase this leaf's producer, not the loss scaler",
            "nonfinite-grads")]

    def check_grad_vectors(self, executable: str, paths: list,
                           nonfinite: list, maxabs: list,
                           loss_scale: Optional[float] = None
                           ) -> list[str]:
        """Vector form of :meth:`check_grad_stats` — the engine hands
        the fused reduction's per-leaf count/max vectors straight
        through; the common all-finite step pays one sum, no zip."""
        if sum(int(n) for n in nonfinite) == 0:
            with self._lock:
                self.counters["checked_steps"] += 1
            return []
        return self.check_grad_stats(
            executable, zip(paths, nonfinite, maxabs),
            loss_scale=loss_scale)

    # -- inference probes ----------------------------------------------
    def check_logits(self, executable: str, nonfinite: int,
                     max_abs: float) -> list[str]:
        """Opt-in v2 dispatch logits-range probe."""
        with self._lock:
            self.counters["checked_steps"] += 1
        if int(nonfinite) > 0:
            return [self._fail(
                f"executable '{executable}': {int(nonfinite)} "
                "non-finite logit(s) in the dispatched batch",
                "nonfinite-logits")]
        if float(max_abs) > self.logits_limit:
            return [self._fail(
                f"executable '{executable}': max|logit|="
                f"{float(max_abs):.3e} exceeds the configured "
                f"limit {self.logits_limit:g} — the pre-NaN "
                "saturation signature (mis-scaled KV cache or "
                "unbounded residual growth)", "logits-range")]
        return []

    def check_kv_scales(self, executable: str, nonfinite: int,
                        max_scale: float) -> list[str]:
        """Opt-in probe over the quantized KV pools' scale slabs."""
        with self._lock:
            self.counters["checked_steps"] += 1
        if int(nonfinite) > 0:
            return [self._fail(
                f"executable '{executable}': {int(nonfinite)} "
                "non-finite KV quantization scale(s) in the pools — "
                "a non-finite activation was quantized into the cache "
                f"(max finite scale {float(max_scale):.3e})",
                "nonfinite-kv-scale")]
        return []

    # -- quantize-site saturation --------------------------------------
    def report_saturation(self, site: str, ratio: float) -> None:
        """Record one quantize site's saturating-code fraction (called
        from ``jax.debug.callback`` on the runtime's callback thread —
        findings are deferred to :meth:`drain`)."""
        ratio = float(ratio)
        with self._lock:
            self.counters["saturation_reports"] += 1
            self.last_saturation[site] = ratio
            if ratio > self.max_saturation.get(site, 0.0):
                self.max_saturation[site] = ratio
        _set_gauge("ds_numsan_saturation_ratio",
                   "fraction of quantized codes at the clip boundary, "
                   "per quantize site", ratio, site=site)
        if ratio > self.saturation_ceiling:
            self._fail(
                f"quantize site '{site}': saturating-code fraction "
                f"{ratio:.4f} exceeds the configured ceiling "
                f"{self.saturation_ceiling:g} — values are being "
                "silently clipped at the quantization boundary "
                "(shrink the block/vector scale granularity, widen "
                "the wire dtype, or clip upstream deliberately)",
                "saturation", defer=True)

    # -- finding plumbing ----------------------------------------------
    def _fail(self, msg: str, kind: str, defer: bool = False) -> str:
        with self._lock:
            self.counters["violations"] += 1
            self.violation_log.append(msg)
            del self.violation_log[:-_LOG_CAP]
        _count_violation("ds_numsan_violations_total", kind)
        if self.mode == "raise":
            if defer:
                with self._lock:
                    self._pending.append(msg)
                return msg
            raise NumSanError(f"numsan: {msg}")
        from ..utils.logging import logger
        logger.warning(f"numsan: {msg}")
        return msg

    def drain(self) -> None:
        """Raise the first deferred (in-graph callback) finding, if
        any. Host choke points call this once per dispatch; warn mode
        never defers, so this is a no-op there."""
        with self._lock:
            pending, self._pending = list(self._pending), []
        if pending and self.mode == "raise":
            raise NumSanError(f"numsan: {pending[0]}")

    # -- reporting -----------------------------------------------------
    def snapshot(self) -> dict:
        """Hang-dump / forensics view (telemetry/flightrec.py embeds
        this next to blocksan's and meshsan's sections)."""
        with self._lock:
            return {
                "mode": self.mode,
                "saturation_ceiling": self.saturation_ceiling,
                "counters": dict(self.counters),
                "violations": list(self.violation_log[-16:]),
                "pending": len(self._pending),
                "saturation": {s: round(r, 6)
                               for s, r in self.last_saturation.items()},
                "saturation_max": {
                    s: round(r, 6)
                    for s, r in self.max_saturation.items()},
            }


# --- process-wide handle (probes + hang dumps) ----------------------------
# Engines register their sanitizer here; the quantize-site probes and
# the hang watchdog read it back without holding an engine reference
# (last-enabled wins — exact for one-engine processes).

_SAN: Optional[NumericsSanitizer] = None


def get_numsan() -> Optional[NumericsSanitizer]:
    return _SAN


def set_numsan(san: Optional[NumericsSanitizer]) -> None:
    global _SAN
    _SAN = san


def env_enabled() -> bool:
    """The ``DS_NUMSAN=1`` env knob (conftest/CI opt-in), mirroring
    ``DS_GRAFTSAN``/``DS_MESHSAN``."""
    return os.environ.get("DS_NUMSAN", "") not in ("", "0")
