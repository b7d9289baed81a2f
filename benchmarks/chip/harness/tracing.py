"""The device trace: record it around a window, and reduce it to numbers.

``capture`` wraps JAX's profiler (host Python tracing off) and marks the
window with a host annotation, which ties the profiler's clock to the host
clock the benchmark keeps.  ``load`` turns the ``.xplane.pb`` into a small
plain structure, and the reductions work on that structure alone, so a
recorded excerpt (``tests/data/``) checks them without a chip:

    {"offset_s": <profiler seconds minus host perf_counter seconds>,
     "devices": [{"name": "/device:TPU:0",
                  "programs": [[name, start_s, dur_s], ...]}]}

Times are seconds on the host clock (``time.perf_counter``).  The unit is
the program execution (the device's ``XLA Modules`` line): a device is busy
while one of its programs runs.  The per-operation line is not read: a
serving step's scans emit some 600,000 operations a second on a v5e, the
profiler drops buffers at that rate, and a reduction from them would count
busy time short.
"""

from __future__ import annotations

import contextlib
import glob
import time
from pathlib import Path

MARK = "chipbench_window"
PROGRAM_LINE = "XLA Modules"


@contextlib.contextmanager
def capture(log_dir: Path):
    """Profile the body; yields the host time at which it started."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(MARK):
            yield time.perf_counter()
    finally:
        jax.profiler.stop_trace()


def _program_name(name: str) -> str:
    """``jit__decode(1234)`` -> ``jit__decode``."""
    return name.split("(")[0].strip()


def load(log_dir: Path, host_start: float) -> dict:
    """Reduce the newest profile under ``log_dir`` to the plain structure;
    ``host_start`` is what :func:`capture` yielded."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        return {"offset_s": 0.0, "devices": []}
    pd = ProfileData.from_file(files[-1])
    mark = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARK and (mark is None
                                            or ev.start_ns < mark):
                        mark = ev.start_ns
            continue
        if not plane.name.startswith("/device:"):
            continue
        rows = [[_program_name(ev.name), ev.start_ns * 1e-9,
                 ev.duration_ns * 1e-9]
                for line in plane.lines if line.name == PROGRAM_LINE
                for ev in line.events]
        if rows:
            devices.append({"name": plane.name, "programs": rows})
    offset = (mark * 1e-9 - host_start) if mark is not None else 0.0
    for dev in devices:
        dev["programs"] = [[n, s - offset, d] for n, s, d in dev["programs"]]
    return {"offset_s": offset, "devices": devices}


def device(trace: dict, index: int) -> dict | None:
    """The plane of device ``index`` (``/device:TPU:<index>``)."""
    for dev in trace["devices"]:
        if dev["name"].rsplit(":", 1)[-1] == str(index):
            return dev
    return None


def clip(intervals, lo: float, hi: float) -> list:
    """``[(start, end)]`` of ``[name, start, dur]`` rows, clipped to
    ``[lo, hi]``; rows wholly outside are dropped."""
    out = []
    for row in intervals:
        s, e = max(row[1], lo), min(row[1] + row[2], hi)
        if e > s:
            out.append((s, e))
    return out


def union(spans) -> list:
    """Merge overlapping ``(start, end)`` spans."""
    merged = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_s(dev: dict, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which some program ran on the device."""
    return sum(e - s for s, e in union(clip(dev["programs"], lo, hi)))


def gaps(dev: dict, lo: float, hi: float) -> list:
    """``(start, end)`` of the idle stretches of ``[lo, hi]``."""
    out, t = [], lo
    for s, e in union(clip(dev["programs"], lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def program_time(dev: dict, prefix: str, lo: float, hi: float) -> tuple:
    """(seconds, calls) of programs whose name starts with ``prefix`` and
    that started inside ``[lo, hi]``."""
    rows = [r for r in dev["programs"]
            if r[0].startswith(prefix) and lo <= r[1] < hi]
    return sum(r[2] for r in rows), len(rows)


def top_programs(devs, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` programs that took most device time, summed over ``devs``
    and averaged over them."""
    tot: dict = {}
    for dev in devs:
        for name, s, d in dev["programs"]:
            if lo <= s < hi:
                tot[name] = tot.get(name, 0.0) + d
    k = max(len(devs), 1)
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(gap_list, spans, n: int = 10) -> list:
    """The ``n`` longest gaps, each named by the host span open at its
    middle (``spans``: ``(name, start, end)`` on the host clock, innermost
    last wins), or ``"no host span"``."""
    out = []
    for s, e in sorted(gap_list, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        name = "no host span"
        best = None
        for sn, ss, se in spans:
            if ss <= mid <= se and (best is None or se - ss < best):
                name, best = sn, se - ss
        out.append([name, e - s])
    return out
