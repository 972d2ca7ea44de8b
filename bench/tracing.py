"""The profiler trace of a window, and its reduction to device numbers.

``capture`` records a JAX profiler trace of the window and marks the
window with a host annotation, so host-clock spans can be placed on the
trace's clock.  ``events`` flattens the trace to plain tuples, and
``reduce`` turns those tuples, the host spans and the window into the
device numbers: busy and idle time per chip, the engine programs' time,
the programs that took the most time, and the idle time by what the host
was doing.  ``reduce`` takes only plain data, so
``tests/test_bench_tracing.py`` checks it on a small recorded trace.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import tempfile
import time

import numpy as np

WINDOW_MARK = "bench_window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# every fused switch engine (serial, affine, staged) compiles as the jit
# of the inner ``run`` of ``core/engine.py``'s ``_fused_engine_impl``
ENGINE_MODULE = "jit_run"
IN_CALL = "unspanned"          # inside run_batch / drain, outside a span
OUTSIDE = "client"             # between calls: the loop, arrivals


class Capture:
    """The trace's events and where the window sat on the host clock."""
    events = None
    t0 = t1 = None


@contextlib.contextmanager
def capture(enabled):
    """Trace the body when ``enabled``; the trace is written under
    ``TMPDIR`` and read by ``events`` before the directory goes away."""
    cap = Capture()
    if not enabled:
        yield cap
        return
    import jax
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_MARK):
                cap.t0 = time.perf_counter()
                yield cap
                cap.t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
        cap.events = events(d)


def events(trace_dir):
    """The events ``reduce`` reads, as (plane, line, name, start_ns,
    dur_ns): the device planes' op and program lines, and the host's
    window mark."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            device = plane.name.startswith(DEVICE_PLANE)
            for line in plane.lines:
                if device and line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    if device or ev.name == WINDOW_MARK:
                        out.append((plane.name, line.name, ev.name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns)))
    return out


def _union(starts, ends):
    """Merge intervals; returns sorted disjoint (starts, ends)."""
    if not len(starts):
        return np.zeros(0), np.zeros(0)
    o = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts)[o], np.asarray(ends)[o]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(run_end[idx[1:] - 1], run_end[-1])


def _inside(points, starts, ends):
    """Index of the disjoint sorted interval holding each point, or -1."""
    i = np.searchsorted(starts, points, side="right") - 1
    ok = (i >= 0) & (points < np.where(i >= 0, ends[np.maximum(i, 0)], 0))
    return np.where(ok, i, -1)


def reduce(evs, spans, calls, window, mark_t0):
    """Device numbers of one traced window.

    ``evs``: the trace's (plane, line, name, start_ns, dur_ns) tuples.
    ``spans``: host spans as (label, t0, t1) on the host clock (seconds).
    ``calls``: the window's run_batch + drain calls as (t0, t1), host
    clock.  ``window``: (t0, t1) on the host clock.  ``mark_t0``: the host
    clock when the window's annotation began.  Returns None when the trace
    holds no device events.
    """
    marks = [e for e in evs if e[2] == WINDOW_MARK]
    dev = [e for e in evs if e[0].startswith(DEVICE_PLANE)]
    if not marks or not dev:
        return None
    # host clock (s) -> trace clock (ns)
    off = marks[0][3] - mark_t0 * 1e9
    w0, w1 = window[0] * 1e9 + off, window[1] * 1e9 + off
    chips = sorted({e[0] for e in dev})

    def clip(rows):
        s = np.array([r[3] for r in rows], float).reshape(-1)
        e = s + np.array([r[4] for r in rows], float).reshape(-1)
        return np.clip(s, w0, w1), np.clip(e, w0, w1)

    # host activity: spans beat the enclosing call, calls beat the loop
    sp_s = np.array([t0 for _, t0, _ in spans], float) * 1e9 + off
    sp_e = np.array([t1 for _, _, t1 in spans], float) * 1e9 + off
    sp_l = np.array([lab for lab, _, _ in spans], object)
    o = np.argsort(sp_s, kind="stable")
    sp_s, sp_e, sp_l = sp_s[o], sp_e[o], sp_l[o]
    c_s, c_e = _union(np.array([a for a, _ in calls], float) * 1e9 + off,
                      np.array([b for _, b in calls], float) * 1e9 + off)

    by = {}
    for e in dev:
        by.setdefault((e[0], e[1]), []).append(e)
    busy, idle_by, mod_time = [], {}, {}
    for chip in chips:
        ops = by.get((chip, OPS_LINE)) or by.get((chip, MODULES_LINE), [])
        b_s, b_e = _union(*clip(ops))
        busy.append(float((b_e - b_s).sum()) / 1e9)
        mods = by.get((chip, MODULES_LINE), [])
        if mods:
            m_s, m_e = clip(mods)
            for name, dur in zip((m[2] for m in mods), (m_e - m_s) / 1e9):
                mod_time[name] = mod_time.get(name, 0.0) + float(dur)
        # idle: the window less the busy union, split by host activity
        pts = np.unique(np.concatenate([[w0, w1], b_s, b_e, sp_s, sp_e,
                                        c_s, c_e]))
        pts = pts[(pts >= w0) & (pts <= w1)]
        mid, seg = (pts[1:] + pts[:-1]) / 2, np.diff(pts)
        idle = _inside(mid, b_s, b_e) < 0
        si = _inside(mid, sp_s, sp_e) if len(sp_s) else np.full(len(mid), -1)
        ci = _inside(mid, c_s, c_e) if len(c_s) else np.full(len(mid), -1)
        lab = np.where(si >= 0, sp_l[np.maximum(si, 0)] if len(sp_l)
                       else IN_CALL, np.where(ci >= 0, IN_CALL, OUTSIDE))
        for name in np.unique(lab[idle]):
            sec = float(seg[idle & (lab == name)].sum()) / 1e9
            idle_by[str(name)] = idle_by.get(str(name), 0.0) + sec
    n = len(chips)
    engine = sum(v for k, v in mod_time.items()
                 if re.match(ENGINE_MODULE + r"(\W|$)", k))
    top = sorted(mod_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(((k, v / n) for k, v in idle_by.items()),
                  key=lambda kv: -kv[1])[:10]
    return dict(chips=n, busy_s=sum(busy) / n, busy_total_s=sum(busy),
                window_s=(w1 - w0) / 1e9,
                engine_s=engine, device_ops=[[k, v] for k, v in top],
                idle_gaps=[[k, v] for k, v in gaps])
