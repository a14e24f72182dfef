"""Observability layer: in-scan probes, ledger, detectors, attribution.

Five planes, all zero-cost when off:

  1. **In-scan metric probes** (``probes``): ``ObsSpec`` rides
     ``SimConfig.obs`` (default ``None``) and selects per-family counter /
     gauge / histogram registers that accumulate *inside* the scan carry —
     AIMD branch counts, Kalman innovation/NIS per bank, preemptions and
     hard-kills per instance type, the fairshare water level, admission
     rejects and queue-depth percentiles.
  2. **Decision ledger** (``ledger``): a bounded ring buffer in the carry
     recording structured ``(tick, kind, tenant, value)`` events for
     controller decisions, fault injections and backoff transitions,
     drained post-run into typed records.
  3. **Sweep/runtime profiling** (``sim.sweep`` + ``spans`` +
     ``export``): the sweep executor's host spans and per-chunk steps
     land in the profiler's trace beside the device's ops, and process
     counters add up trace, compile and callable-cache misses
     (``spans``); per-chunk wall-clock (the spans' durations),
     compile-vs-execute split and XLA peak-bytes land in the stream
     manifest and a ``SweepReport``; ``export`` renders a run's ledger
     or a sweep's chunk timeline as Chrome/Perfetto trace JSON.
  4. **In-scan anomaly detection** (``detect``): CUSUM/EWMA change-point
     detectors, a chi-square NIS band test over the Kalman innovation
     probes and multi-window SLO burn-rate tracking ride
     ``ObsSpec.detect`` (default ``None``, compiled out) and fire
     fixed-shape alert events — with severity and subject — into the
     ledger ring; ``metrics`` exposes any report as OpenMetrics text and
     live-tails streamed sweep directories.
  5. **Cross-run attribution** (``compare``): diff two ObsReports family
     by family — or two benchmark JSON artifacts leaf by leaf — and
     localize the first divergence; the CI bench gate prints and uploads
     that localization whenever it fails.

Carry-threading contract (what ``sim.runner`` guarantees):

  * ``SimConfig.obs`` is *static* (hashable, part of every jit cache key,
    surviving ``strip_tuned``) and ``None`` by default.  Every probe site
    in the step function is a trace-time conditional on it, and the
    ``SimState.obs`` carry field defaults to ``None`` — a leafless pytree
    — so an ``obs=None`` config compiles a scan structurally identical to
    the pre-obs simulator.  The kind="obs" bench gate pins this with a
    sha256 digest over the default sweep, exactly like ``faults=None``.
  * Probes are *read-only*: they consume values the step already
    computed, draw no PRNG, and feed nothing back, so enabling any probe
    subset leaves the simulation's own results bit-identical.
  * Families are independent: each ``ObsSpec`` flag gates its own carry
    registers and update ops, so enabling one family never pays for —
    or perturbs — another (``tests/test_obs.py`` pins both properties).

This package deliberately imports nothing from ``repro.sim`` or
``repro.core`` (the emission hooks live *there* and hand plain arrays in),
so the core control plane can type against ``ObsSpec`` without an import
cycle.
"""

from . import compare, detect, export, ledger, metrics, probes, spans
from .compare import Divergence, attribution, diff_bench, diff_reports
from .detect import BURN_NAMES, SIGNAL_NAMES, DetectCarry, DetectSpec
from .ledger import KIND_NAMES, Ledger, LedgerRecord
from .metrics import to_openmetrics, watch
from .probes import (ObsCarry, ObsReport, ObsSpec, TickSignals, drain,
                     hist_percentile, init_carry, update)

__all__ = ["compare", "detect", "export", "ledger", "metrics", "probes",
           "spans",
           "BURN_NAMES", "SIGNAL_NAMES", "KIND_NAMES", "Divergence",
           "DetectCarry", "DetectSpec", "Ledger", "LedgerRecord",
           "ObsCarry", "ObsReport", "ObsSpec", "TickSignals",
           "attribution", "diff_bench", "diff_reports", "drain",
           "hist_percentile", "init_carry", "to_openmetrics", "update",
           "watch"]
