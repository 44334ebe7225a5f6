"""Peak-flops table, MFU math, and compiled-memory budgets — one source of
truth.

``bench.py`` grew a hand-rolled device-kind -> peak-bf16-flops table and a
``compiled.cost_analysis()`` extraction for its MFU columns; the
:class:`~apex_tpu.observability.report.StepReporter` wants the same number
as a live gauge. Both now read from here:

- :data:`PEAK_BF16_FLOPS` / :func:`peak_flops` — peak dense bf16 FLOP/s
  per chip by ``device_kind`` prefix (public spec-sheet numbers);
- :func:`flops_budget` — the per-step model FLOPs of a lowered+compiled
  executable via XLA's cost analysis (None when the backend reports
  nothing useful — notably, Mosaic custom calls report zero flops, so GPT
  steps with flash attention should prefer an analytic count);
- :func:`mfu` — model-flops-utilization: achieved FLOP/s over peak;
- :func:`memory_budget` — the executable's static memory plan from
  ``compiled.memory_analysis()`` (argument/output/temp/peak bytes) — the
  number that makes an activation-remat policy choice measurable instead
  of vibes (``StepReporter.attach_memory_budget`` turns it into the
  ``mem/*`` gauge family; ``bench.py`` records it next to step_ms).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, Optional

__all__ = ["PEAK_BF16_FLOPS", "DEFAULT_PEAK_FLOPS", "peak_flops",
           "DeviceSpec", "DEVICE_SPECS", "DEFAULT_DEVICE_SPEC",
           "device_spec", "flops_budget", "memory_budget", "mfu"]

# peak dense bf16 TFLOP/s per chip by device kind (public spec sheets)
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
}

# v5e-class corners for the CPU backend ONLY: there the modeled numbers
# are structure (regions, ratios, byte counts), never a device metric.
# An accelerator whose kind the tables do not know is an error — a
# utilization against an assumed peak would read as a measurement.
DEFAULT_PEAK_FLOPS = 197e12


def _match_kind(table, device, cpu_default):
    """``table`` entry whose key prefixes ``device.device_kind`` (default
    device: the first visible one); ``cpu_default`` for a CPU device,
    ``ValueError`` for any other kind the table has not learned."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    for prefix, value in table.items():
        if kind.startswith(prefix):
            return value
    if getattr(device, "platform", kind) == "cpu":
        return cpu_default
    raise ValueError(
        f"no peak numbers for device kind {kind!r}: add it to "
        "PEAK_BF16_FLOPS / DEVICE_SPECS in "
        "apex_tpu/observability/costs.py with its spec-sheet source "
        f"(known: {sorted(table)})")


def peak_flops(device=None) -> float:
    """Peak dense bf16 FLOP/s of ``device`` (default: the first visible
    device), matched by ``device_kind`` prefix against
    :data:`PEAK_BF16_FLOPS`. A CPU device gets the labelled
    :data:`DEFAULT_PEAK_FLOPS`; an unknown accelerator kind raises."""
    return _match_kind(PEAK_BF16_FLOPS, device, DEFAULT_PEAK_FLOPS)


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Roofline corners of one chip: peak dense bf16 FLOP/s, HBM bandwidth
    and per-link ICI bandwidth. The numbers the pyprof roofline evaluator
    (:mod:`apex_tpu.pyprof.model`) divides modeled FLOPs/bytes by."""
    name: str
    peak_flops: float   # dense bf16 FLOP/s per chip
    hbm_gbps: float     # HBM bandwidth, GB/s per chip
    ici_gbps: float     # ICI bandwidth, GB/s per link per direction

    def compute_ms(self, flops: float) -> float:
        return flops / self.peak_flops * 1e3

    def hbm_ms(self, traffic_bytes: float) -> float:
        return traffic_bytes / (self.hbm_gbps * 1e9) * 1e3

    def comm_ms(self, wire_bytes: float) -> float:
        return wire_bytes / (self.ici_gbps * 1e9) * 1e3


# HBM/ICI companions to PEAK_BF16_FLOPS (public spec-sheet numbers; ICI
# is per link per direction — the ring models in pyprof serialize hops
# over one link, the worst-case topology). Env-overridable via
# APEX_TPU_PEAK_FLOPS / APEX_TPU_HBM_GBPS / APEX_TPU_ICI_GBPS, for
# calibrating the roofline against a measured bandwidth instead of the
# datasheet.
DEVICE_SPECS = {
    "TPU v4": DeviceSpec("TPU v4", PEAK_BF16_FLOPS["TPU v4"], 1228.0, 50.0),
    "TPU v5 lite": DeviceSpec("TPU v5e", PEAK_BF16_FLOPS["TPU v5e"],
                              819.0, 50.0),
    "TPU v5e": DeviceSpec("TPU v5e", PEAK_BF16_FLOPS["TPU v5e"],
                          819.0, 50.0),
    "TPU v5": DeviceSpec("TPU v5p", PEAK_BF16_FLOPS["TPU v5p"],
                         2765.0, 100.0),
    "TPU v5p": DeviceSpec("TPU v5p", PEAK_BF16_FLOPS["TPU v5p"],
                          2765.0, 100.0),
    "TPU v6 lite": DeviceSpec("TPU v6e", PEAK_BF16_FLOPS["TPU v6e"],
                              1640.0, 100.0),
    "TPU v6e": DeviceSpec("TPU v6e", PEAK_BF16_FLOPS["TPU v6e"],
                          1640.0, 100.0),
}

# the CPU backend's stand-in (see DEFAULT_PEAK_FLOPS): on CPU the modeled
# milliseconds are structural, not predictive — the regions, ratios and
# byte counts are what the tests pin down
DEFAULT_DEVICE_SPEC = DeviceSpec("cpu (v5e-class corners assumed)",
                                 DEFAULT_PEAK_FLOPS, 819.0, 50.0)


def device_spec(device=None) -> DeviceSpec:
    """The :class:`DeviceSpec` of ``device`` (default: first visible
    device), matched by ``device_kind`` prefix; a CPU device gets
    :data:`DEFAULT_DEVICE_SPEC`, an unknown accelerator kind raises.
    ``APEX_TPU_PEAK_FLOPS`` (FLOP/s), ``APEX_TPU_HBM_GBPS`` and
    ``APEX_TPU_ICI_GBPS`` (GB/s) override the matched table entry
    field-by-field."""
    spec = _match_kind(DEVICE_SPECS, device, DEFAULT_DEVICE_SPEC)
    overrides = {}
    for env, field in (("APEX_TPU_PEAK_FLOPS", "peak_flops"),
                       ("APEX_TPU_HBM_GBPS", "hbm_gbps"),
                       ("APEX_TPU_ICI_GBPS", "ici_gbps")):
        raw = os.environ.get(env)
        if raw:
            value = float(raw)
            if value <= 0.0:
                raise ValueError(f"{env} must be positive, got {raw!r}")
            overrides[field] = value
    if overrides:
        spec = dataclasses.replace(spec, name=spec.name + " (env-tuned)",
                                   **overrides)
    return spec


def flops_budget(compiled) -> Optional[float]:
    """Per-execution model FLOPs of a compiled executable
    (``jit(f).lower(...).compile()``), from XLA's cost analysis.

    Returns None when the backend exposes no cost analysis or reports a
    non-positive/non-finite count (custom calls — e.g. Mosaic flash
    attention — report zero flops and would deflate MFU; callers should
    fall back to an analytic count, as ``bench.py`` does).
    """
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost["flops"])
    except Exception:
        return None
    if not (0.0 < flops < float("inf")):  # rejects NaN, ±inf, <= 0
        return None
    return flops


def memory_budget(compiled) -> Optional[Dict[str, int]]:
    """Static memory plan of a compiled executable
    (``jit(f).lower(...).compile()``), from ``compiled.memory_analysis()``.

    Returns None when the backend exposes no analysis; otherwise a dict of

    - ``argument_bytes`` / ``output_bytes`` — buffers entering/leaving the
      program (donated/aliased bytes already netted out via
      ``alias_bytes``);
    - ``temp_bytes`` — XLA's scratch high-water for the program body: the
      activation/residual working set. THIS is the number an activation-
      remat policy moves (``none > selective > full`` on a train step);
    - ``alias_bytes`` — input/output-aliased (donated) bytes;
    - ``generated_code_bytes`` — the program text itself;
    - ``host_temp_bytes`` — host-memory scratch: nonzero exactly when an
      ``offload`` remat policy (or any host-memory placement) is in play;
    - ``peak_hbm_bytes`` — the device high-water estimate
      ``argument + output + temp + generated_code - alias`` (the standard
      XLA accounting: arguments and outputs are resident for the whole
      program, donation collapses the aliased pairs).
    """
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None

    def _get(attr: str) -> int:
        return int(getattr(ma, attr, 0) or 0)

    out = {
        "argument_bytes": _get("argument_size_in_bytes"),
        "output_bytes": _get("output_size_in_bytes"),
        "temp_bytes": _get("temp_size_in_bytes"),
        "alias_bytes": _get("alias_size_in_bytes"),
        "generated_code_bytes": _get("generated_code_size_in_bytes"),
        "host_temp_bytes": _get("host_temp_size_in_bytes"),
    }
    out["peak_hbm_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                             + out["temp_bytes"]
                             + out["generated_code_bytes"]
                             - out["alias_bytes"])
    return out


def mfu(flops_per_step: float, step_time_s: float,
        peak: Optional[float] = None) -> float:
    """Model-flops-utilization: ``flops_per_step / step_time_s / peak``
    (``peak`` defaults to :func:`peak_flops` of the first device).

    A non-positive ``step_time_s`` or ``peak`` returns ``NaN`` instead of
    raising: the first-report wall-time delta in a tight loop can
    legitimately be ~0 on a fast host (two ``perf_counter`` reads between
    cached dispatches), and an exception or ``inf``/``ZeroDivisionError``
    mid-``report()`` would kill the training loop over a telemetry
    artifact. Consumers that want a hard failure should validate inputs
    at configuration time (``StepReporter.attach_flops_budget`` does).
    """
    if peak is None:
        peak = peak_flops()
    if step_time_s <= 0.0 or peak <= 0.0:
        return math.nan
    return flops_per_step / step_time_s / peak
