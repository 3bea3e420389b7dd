"""One run of one cell: device check, compile cache, the entry's set-up,
window and check, the metric readers, and the result line.

Standard output ends with one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with its limit).  Standard error
ends with the same numbers, one per line.  Without a TPU, or with fewer
chips than the cell asks for, the run prints no result and exits 3.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from bench.catalog import ROOT, Catalog

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
NO_DEVICE = 3


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileClock:
    """Programs JAX made ready since construction, from ``jax.monitoring``
    events: ``compiled`` counts every backend compile, whether it ran the
    compiler or read the program back from the persistent cache, and
    ``hits`` the reads."""

    def __init__(self):
        import jax  # noqa: PLC0415

        self.seconds, self.compiled, self.hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == COMPILE_EVENT:
                self.seconds += secs
                self.compiled += 1

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def cold(self) -> bool:
        """Whether the compiler ran for some program."""
        return self.compiled > self.hits

    def describe(self) -> str:
        return (f"{'cold' if self.cold else 'warm'}: {self.compiled} programs "
                f"({self.seconds:.1f} s), {self.hits} from the persistent cache")


class Tracer:
    """Profiles one span of the window when ``--trace 1``.  The entry calls
    ``start`` and ``stop`` around steady work; both are no-ops otherwise.
    ``paused_s`` is the time the two took: the host does nothing else
    meanwhile."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = False
        self.done = not enabled
        self.paused_s = 0.0
        self._window = None

    def prime(self) -> None:
        """Starts and stops the profiler once, in set-up: its first start
        takes tens of seconds, which must not fall in the window."""
        if not self.enabled:
            return
        import jax  # noqa: PLC0415

        jax.profiler.start_trace(str(TRACE_DIR))
        jax.profiler.stop_trace()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def start(self) -> None:
        if not self.enabled or self.active or self.done:
            return
        import jax  # noqa: PLC0415

        t = time.monotonic()
        jax.profiler.start_trace(str(TRACE_DIR))
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.active = True
        self.paused_s += time.monotonic() - t
        log(f"[trace] started in {time.monotonic() - t:.3f} s")

    def stop(self) -> None:
        if not self.active:
            return
        import jax  # noqa: PLC0415

        t = time.monotonic()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active, self.done = False, True
        self.paused_s += time.monotonic() - t
        log(f"[trace] stopped in {time.monotonic() - t:.3f} s")

    def summary(self):
        if not (self.enabled and self.done):
            return None
        from bench import trace_reduce  # noqa: PLC0415

        try:
            return trace_reduce.reduce_dir(TRACE_DIR)
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax  # noqa: PLC0415

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Check:
    """One number the check compares, with its limit (``value <= limit``)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a run leaves for the metric readers.  Entries fill ``data``
    with their own records; see ``bench/entries``.  ``reference`` is the
    plain reference module the configuration names."""

    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    peak: dict
    reference: Any = None
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    checks: List[Check] = dataclasses.field(default_factory=list)
    trace: Any = None
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py", description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax_cache() -> None:
    """The persistent compilation cache, at one fixed place in the
    checkout; every program is kept, however fast it compiled and however
    large the cache grows: a size limit set in the environment would evict
    programs that the next run reads back, and a cell whose programs pass
    it would compile in every run.  Set before JAX starts, so the program's
    own cache helper takes the same one.  The TPU runtime's logs go under
    ``TMPDIR``, not to a fixed path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax  # noqa: PLC0415

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def find_devices(chips: int, require_tpu: bool = True):
    """The devices JAX found, or None (with a reason on stderr) where they
    are not TPUs or fewer than ``chips``."""
    import jax  # noqa: PLC0415

    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)}")
    if require_tpu and dev.platform != "tpu":
        log("[device] no TPU found: no result")
        return None
    if len(devices) < chips:
        log(f"[device] the cell needs {chips} chips, {len(devices)} found: no result")
        return None
    return devices


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def measure(run: Run, cat: Catalog, per_layer: bool) -> Dict[str, dict]:
    out = {}
    for m in cat.metrics(run.workload["name"], per_layer):
        value = cat.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(args, cat: Catalog, t_start: float,
            require_tpu: bool = True) -> Optional[dict]:
    """Runs one cell; returns the result object, or None where the devices
    do not fit the cell.  ``require_tpu=False`` lets the tests drive a run
    on the CPU at a reduced size."""
    cell = cat.workload(args.workload)
    config = cat.config(cell["config"])
    traffic = cat.traffic(cell["traffic"])
    limits = cat.limits(args.workload)
    configure_jax_cache()
    devices = find_devices(cell["chips"], require_tpu)
    if devices is None:
        return None
    from bench import work  # noqa: PLC0415

    dev = devices[0]
    peak = work.peaks(dev.device_kind) if require_tpu else work.peaks("TPU v5 lite")
    clock = CompileClock()
    tracer = Tracer(bool(args.trace))
    tracer.prime()
    run = Run(workload=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, peak=peak, reference=cat.reference(config))
    entry = importlib.import_module(f"bench.entries.{config['system']['entry']}")
    entry.run_cell(run, limits=limits, clock=clock, tracer=tracer,
                   t_start=t_start, devices=devices)
    run.trace = tracer.summary()
    run.data["trace_pause_s"] = tracer.paused_s
    if run.trace is not None:
        top = sorted(run.trace.module_s.items(), key=lambda kv: -kv[1])[:12]
        log("[trace] programs: " + "; ".join(
            f"{k} {v:.4f} s x{run.trace.module_count[k]}" for k, v in top))
    result = {
        "correct": all(c.ok for c in run.checks) and bool(run.checks),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": measure(run, cat, per_layer=bool(args.trace)),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": run.memory_peak_bytes},
    }
    if run.trace is not None:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [[k[:200], v] for k, v in run.trace.top_ops()],
            "idle_gaps": [[k, v] for k, v in run.trace.top_idle()],
        }
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    return result


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    result = execute(args, Catalog(), t_start)
    if result is None:
        return NO_DEVICE
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r} {ok}")
    print(json.dumps(result), flush=True)
    return 0
