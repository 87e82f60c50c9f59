"""XLA cost-model extraction + roofline attribution (performance accounting).

A hand-written flops-per-row formula is a guess.
This module uses XLA's own accounting instead: every compiled entry
point (the fused train block, each frontier wave-width bucket's histogram
sweep, each serving predict bucket, the materialize flush) is AOT-lowered
and compiled once, and its static costs — FLOPs, bytes accessed, peak /
temp / output memory — are read from ``Compiled.cost_analysis()`` +
``Compiled.memory_analysis()``.  Combined with measured wall time (span
summaries from obs/trace.py) that yields
per-phase roofline attribution: achieved FLOP/s, achieved B/s, arithmetic
intensity, and ``mfu`` / ``membw_util`` against the detected chip's peaks.
Both GPU GBDT papers (arXiv:1706.08359, 1806.11248) argue from exactly
this accounting — histogram accumulation is memory-bound, so achieved
bytes/s against the roofline is the number that matters.

Extraction discipline (pinned by tests/test_costmodel.py):

- it is PULL-based: nothing in the training loop triggers it, so
  ``observability=none`` runs emit zero costmodel work;
- AOT lowering shares nothing with the executing program — extraction
  never recompiles or alters a training/serving executable (their jaxprs
  are byte-identical before/after, and dispatching them after extraction
  adds zero backend compiles);
- the first extraction of a program pays its own one AOT compile (the
  ``__call__`` and AOT executable caches are disjoint in this jax); every
  repeat is served from the in-process cache, and when a persistent
  compile cache is configured (``compile_cache_dir``) the extracted
  numbers are ALSO persisted next to it (``costmodel_cache.json``), so a
  warm process does no jax work at all — not even tracing.

On CPU there is no meaningful peak to normalize by, so rooflines report
achieved rates without a utilization ratio (``detect_peaks`` -> None).

This module imports jax only inside functions — the stats server route
(``GET /roofline``) must stay importable in processes that never touch a
device.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..log import Log, LightGBMError
from .registry import MetricsRegistry, get_registry

# ------------------------------------------------------------ chip peaks
# Public per-chip peaks: bf16 matmul FLOP/s and HBM bandwidth (bytes/s).
# The one definition of the roofline denominator, keyed by what
# normalize_device_kind makes of the PJRT device_kind.
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "v4": {"flops_per_s": 275e12, "hbm_bytes_per_s": 1.228e12},
    "v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 0.819e12},
    "v5p": {"flops_per_s": 459e12, "hbm_bytes_per_s": 2.765e12},
    "v6e": {"flops_per_s": 918e12, "hbm_bytes_per_s": 1.640e12},
    "trillium": {"flops_per_s": 918e12, "hbm_bytes_per_s": 1.640e12},
}


def normalize_device_kind(kind: str) -> str:
    """Normalize a PJRT ``device_kind`` string to something the peaks
    table can be matched against ('TPU v5 lite' -> 'tpuv5e')."""
    k = str(kind or "").lower().replace(" ", "").replace("_", "")
    return k.replace("v6lite", "v6e").replace("v5lite", "v5e")


def detect_peaks(device_kind: Optional[str] = None
                 ) -> Optional[Dict[str, float]]:
    """Peak FLOP/s + HBM B/s for the chip generation running this
    process (or for an explicit ``device_kind`` string).  Returns None
    on CPU and other non-TPU hosts: a roofline there reports achieved
    rates only.  A TPU whose generation is not in ``CHIP_PEAKS`` raises
    — a utilization ratio against another chip's peak is worse than
    none."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    kind = normalize_device_kind(device_kind)
    for key, peaks in CHIP_PEAKS.items():
        if key in kind:
            return dict(peaks)
    if "tpu" in kind:
        raise LightGBMError(
            "device_kind %r is a TPU generation CHIP_PEAKS does not list "
            "(%s); add its published peaks before reporting utilization"
            % (device_kind, ", ".join(sorted(CHIP_PEAKS))))
    return None


# ------------------------------------------------------------ extraction
def costs_from_compiled(compiled) -> Dict[str, float]:
    """Normalize ``Compiled.cost_analysis()`` + ``memory_analysis()``
    into one flat dict.  memory_analysis has no
    ``peak_memory_in_bytes`` here, so peak is derived as
    argument + output + temp - alias."""
    ca = compiled.cost_analysis() or {}

    def _pos(key):
        try:
            v = float(ca.get(key, 0.0))
        except (TypeError, ValueError):
            return 0.0
        return v if v > 0.0 else 0.0     # -1 marks "not implemented"

    out = {"flops": _pos("flops"),
           "bytes_accessed": _pos("bytes accessed"),
           "transcendentals": _pos("transcendentals")}
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - optional on some backends
        ma = None
    if ma is not None:
        arg = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
        outb = float(getattr(ma, "output_size_in_bytes", 0) or 0)
        tmp = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
        alias = float(getattr(ma, "alias_size_in_bytes", 0) or 0)
        peak = float(getattr(ma, "peak_memory_in_bytes", 0) or 0)
        out.update(
            argument_bytes=arg, output_bytes=outb, temp_bytes=tmp,
            alias_bytes=alias,
            peak_bytes=peak if peak > 0 else max(arg + outb + tmp - alias,
                                                 0.0),
            generated_code_bytes=float(
                getattr(ma, "generated_code_size_in_bytes", 0) or 0))
    return out


def _leaf_signature(leaf) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return "%s[%s]" % (dtype, ",".join(map(str, shape)))
    return repr(leaf)


class CostModel:
    """Per-process store of per-entry static costs.

    ``analyze(name, fn, *args, **kwargs)`` AOT-lowers + compiles the jit
    function on the given arg shapes (``jax.ShapeDtypeStruct`` mirrors
    work — no real arrays needed), extracts its costs, registers them as
    gauges (``lgbm_costmodel_*{entry=name}``) and caches the result by
    (name, backend, jax version, arg signature) — in memory always, and
    on disk next to jax's persistent compile cache when one is
    configured.  A cache hit does zero jax work.
    """

    DISK_CACHE_NAME = "costmodel_cache.json"

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 cache_dir: Optional[str] = None):
        self.registry = registry if registry is not None else get_registry()
        self._cache_dir = cache_dir
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict[str, float]] = {}
        self._by_key: Dict[str, Dict[str, float]] = {}
        self._c_extract = self.registry.counter(
            "lgbm_costmodel_extractions_total",
            "Cost-model extraction requests (including cache hits).")
        self._c_compiles = self.registry.counter(
            "lgbm_costmodel_aot_compiles_total",
            "AOT compiles the cost model actually paid (cache misses).")

    # ------------------------------------------------------------ cache
    def _disk_path(self) -> str:
        d = self._cache_dir
        if not d:
            try:
                import jax
                d = jax.config.jax_compilation_cache_dir or ""
            except Exception:  # noqa: BLE001
                d = ""
        return os.path.join(d, self.DISK_CACHE_NAME) if d else ""

    def _disk_load(self) -> Dict[str, Dict[str, float]]:
        path = self._disk_path()
        if not path or not os.path.exists(path):
            return {}
        try:
            with open(path) as fh:
                data = json.load(fh)
            return data if isinstance(data, dict) else {}
        except Exception:  # noqa: BLE001 - a bad cache means no cache
            return {}

    def _disk_store(self, key: str, name: str,
                    costs: Dict[str, float]) -> None:
        path = self._disk_path()
        if not path:
            return
        try:
            data = self._disk_load()
            data[key] = {"entry": name, "costs": costs}
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(data, fh, sort_keys=True)
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001 - persistence is best-effort
            pass

    def _key(self, name: str, args, kwargs, extra_key: str) -> str:
        import jax
        leaves = jax.tree_util.tree_leaves((args, tuple(sorted(
            (k, v) for k, v in kwargs.items()))))
        sig = ";".join(_leaf_signature(x) for x in leaves)
        raw = "|".join((name, jax.version.__version__,
                        jax.default_backend(), extra_key, sig))
        return hashlib.sha256(raw.encode()).hexdigest()[:32]

    # ------------------------------------------------------------ public
    def analyze(self, name: str, fn, *args, extra_key: str = "",
                **kwargs) -> Dict[str, float]:
        """Extract (or recall) the static costs of ``fn`` at these arg
        shapes and publish them under entry label ``name``.  ``fn`` must
        be a jit-wrapped callable (has ``.lower``); static kwargs pass
        through to it.  Never raises past jax errors: a failed lowering
        propagates so callers see real mistakes, but cache/IO problems
        degrade silently."""
        self._c_extract.inc()
        key = self._key(name, args, kwargs, extra_key)
        with self._lock:
            hit = self._by_key.get(key)
        if hit is None:
            disk = self._disk_load().get(key)
            if disk and isinstance(disk.get("costs"), dict):
                hit = {k: float(v) for k, v in disk["costs"].items()}
        if hit is None:
            compiled = fn.lower(*args, **kwargs).compile()
            self._c_compiles.inc()
            hit = costs_from_compiled(compiled)
            self._disk_store(key, name, hit)
        with self._lock:
            self._by_key[key] = hit
            self._entries[name] = hit
        self._publish(name, hit)
        return dict(hit)

    def record(self, name: str, costs: Dict[str, float]) -> None:
        """Register externally-computed costs under ``name`` (used by
        callers that already hold a Compiled object)."""
        costs = {k: float(v) for k, v in costs.items()}
        with self._lock:
            self._entries[name] = costs
        self._publish(name, costs)

    def _publish(self, name: str, costs: Dict[str, float]) -> None:
        lbl = {"entry": name}
        for field, metric, help_txt in (
                ("flops", "lgbm_costmodel_flops",
                 "XLA cost-analysis FLOPs per call of this entry point."),
                ("bytes_accessed", "lgbm_costmodel_bytes_accessed",
                 "XLA cost-analysis bytes accessed per call."),
                ("peak_bytes", "lgbm_costmodel_peak_bytes",
                 "Peak device memory of the compiled executable."),
                ("temp_bytes", "lgbm_costmodel_temp_bytes",
                 "Temp-buffer bytes of the compiled executable."),
                ("output_bytes", "lgbm_costmodel_output_bytes",
                 "Output bytes of the compiled executable.")):
            if field in costs:
                self.registry.gauge(metric, help_txt,
                                    labels=lbl).set(costs[field])

    def entries(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    def get(self, name: str) -> Optional[Dict[str, float]]:
        with self._lock:
            e = self._entries.get(name)
            return dict(e) if e is not None else None


_COSTMODEL = CostModel()


def get_cost_model() -> CostModel:
    """The process-wide cost model (parallel to obs.registry's
    get_registry): boosters, serving and the tools all publish here so
    one ``/roofline`` scrape sees every extracted entry point."""
    return _COSTMODEL


# ------------------------------------------------------------ roofline
def roofline_row(name: str, costs: Dict[str, float], seconds: float,
                 calls: float,
                 peaks: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """One per-phase attribution row: static per-call costs x measured
    wall time -> achieved rates (+ utilization when peaks are known).
    ``seconds`` is total wall time over ``calls`` dispatches; rows with
    no timing (calls == 0) carry static costs only."""
    flops = float(costs.get("flops", 0.0))
    byts = float(costs.get("bytes_accessed", 0.0))
    row: Dict[str, Any] = {
        "phase": name,
        "calls": float(calls),
        "seconds": round(float(seconds), 6),
        "flops_per_call": flops,
        "bytes_per_call": byts,
    }
    if byts > 0:
        row["arithmetic_intensity"] = round(flops / byts, 6)
    if "peak_bytes" in costs:
        row["peak_bytes"] = float(costs["peak_bytes"])
    if seconds > 0 and calls > 0:
        row["flops_per_s"] = round(flops * calls / seconds, 3)
        row["bytes_per_s"] = round(byts * calls / seconds, 3)
        if peaks:
            pf = float(peaks.get("flops_per_s", 0.0))
            pb = float(peaks.get("hbm_bytes_per_s", 0.0))
            if pf > 0:
                row["mfu"] = round(row["flops_per_s"] / pf, 8)
            if pb > 0:
                row["membw_util"] = round(row["bytes_per_s"] / pb, 8)
            if pf > 0 and pb > 0 and byts > 0:
                # below the ridge point the phase cannot saturate the
                # MXUs no matter how well it is scheduled
                ridge = pf / pb
                row["bound"] = ("memory" if flops / byts < ridge
                                else "compute")
    return row


def roofline_table(wall_times: Dict[str, Tuple[float, float]],
                   cost_model: Optional[CostModel] = None,
                   peaks: Optional[Dict[str, float]] = None,
                   include_static_only: bool = True) -> List[Dict[str, Any]]:
    """Join extracted entries with ``{name: (seconds, calls)}`` wall
    times.  Entries without a timing still appear (static costs only)
    unless ``include_static_only`` is False."""
    cm = cost_model if cost_model is not None else get_cost_model()
    rows = []
    for name, costs in sorted(cm.entries().items()):
        seconds, calls = wall_times.get(name, (0.0, 0.0))
        if calls <= 0 and not include_static_only:
            continue
        rows.append(roofline_row(name, costs, seconds, calls, peaks))
    return rows


def span_wall_times(registry: Optional[MetricsRegistry] = None,
                    metric: str = "lgbm_train_span_seconds"
                    ) -> Dict[str, Tuple[float, float]]:
    """Lifetime (sum_seconds, count) per span name from the tracer's
    summary series — the wall-time side of the roofline join for phases
    that run inside real training (train_block, materialize)."""
    reg = registry if registry is not None else get_registry()
    out: Dict[str, Tuple[float, float]] = {}
    for m in reg.metrics():
        if m.name != metric or m.kind != "summary":
            continue
        span = m.label_dict.get("span")
        if not span:
            continue
        # spans are dotted (train.block), cost-model entries are not
        out[span.replace(".", "_")] = (float(m.total), float(m.count))
    return out


def roofline_snapshot(registry: Optional[MetricsRegistry] = None,
                      cost_model: Optional[CostModel] = None
                      ) -> Dict[str, Any]:
    """The ``GET /roofline`` payload: detected peaks + one attribution
    row per extracted entry point, joined with whatever span wall-times
    the registry holds.  Entries that have no matching span (the
    wave-width buckets) report static costs only."""
    peaks = detect_peaks()
    try:
        import jax
        kind = getattr(jax.devices()[0], "device_kind", "")
        backend = jax.default_backend()
    except Exception:  # noqa: BLE001 - scrape must answer regardless
        kind, backend = "", ""
    rows = roofline_table(span_wall_times(registry), cost_model=cost_model,
                          peaks=peaks)
    return {
        "ts": round(time.time(), 3),
        "backend": backend,
        "device_kind": kind,
        "peaks": peaks,      # None on CPU: achieved rates only
        "rows": rows,
    }
