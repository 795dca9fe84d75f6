"""Per-layer quantities of a traced run, shared by the readers under
``bench/metrics/``.  Each returns a number, or None when the run holds
nothing to read (the harness then leaves the metric out).

``run`` carries: ``config`` (the configuration file, whose model family,
``bench/families/<family>.py``, counts the FLOPs and bytes of the work
the run recorded), ``chips``, ``peak`` (``bench/peaks.py``), ``trace``
(``bench/trace.Trace``) and ``window`` (its traced stretch, ns),
``recorder`` (``harness.Recorder``:
decode launches, finished prefills and step times while tracing),
``counters`` (engine counters at the window's start and end) and
``admitted_prompt_tokens``, and ``notes`` for side facts such as which
roofline bound applies.
"""
from __future__ import annotations

from bench import harness as H
from bench import trace as T

DECODE = "decode_multi_paged"
PREFILL = ("prefill_chunk_paged", "prefill_ragged")


def _devices(run) -> list:
    if run.trace is None or run.window is None:
        return []
    return [run.trace.devices[i] for i in range(run.chips)
            if i in run.trace.devices]


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def step_idle_share(run):
    """Share (%) of the summed wall time of the harness's ``step()`` spans
    in which no operation ran on the device, averaged over the chips."""
    devs = _devices(run)
    lo, hi = run.window or (0, 0)
    steps = [(s, e) for s, e, n in run.trace.spans
             if n == "bench.step" and s >= lo and e <= hi] if devs else []
    total = sum(e - s for s, e in steps)
    if not total:
        return None
    idle = []
    for dev in devs:
        b = T.busy(dev, lo, hi)
        idle.append(sum((e - s) - T.overlap(b, s, e) for s, e in steps))
    return 100.0 * _mean(idle) / total


def decode_step_ms(run):
    """Device time (ms) of one decode launch, averaged over launches and
    chips."""
    devs = _devices(run)
    lo, hi = run.window or (0, 0)
    per = []
    for dev in devs:
        ns, n = T.module_time(dev, DECODE, lo, hi)
        if n:
            per.append(ns / n / 1e6)
    return _mean(per)


def prefill_ms_per_ktok(run):
    """Device time (ms) of the prefill modules per 1,000 prompt tokens
    computed while tracing."""
    devs = _devices(run)
    lo, hi = run.window or (0, 0)
    c0, c1 = run.recorder.counters0, run.recorder.counters1
    if not devs or c0 is None or c1 is None:
        return None
    toks = c1["prefill_tokens"] - c0["prefill_tokens"]
    if toks <= 0:
        return None
    ns = _mean([sum(T.module_time(d, p, lo, hi)[0] for p in PREFILL)
                for d in devs])
    return ns / 1e6 / (toks / 1000.0) if ns else None


def decode_roofline(run):
    """Least time of a decode launch's work over its measured device time
    (%).  The least time is the larger of FLOPs over the chips' peak and
    required bytes over their bandwidth, per chip, both as the family
    counts them (dense: every weight once, the KV of each active lane's
    context and the new token's KV)."""
    dms = decode_step_ms(run)
    launches = run.recorder.decodes
    if dms is None or not launches:
        return None
    f_peak, b_peak = run.peak["bf16_flops"], run.peak["hbm_bytes_per_s"]
    fam = H.family(run.config)
    least, bound = [], {"compute": 0, "memory": 0}
    for ctx in launches:
        fl, by = fam.decode_step(run, ctx)
        tf, tb = fl / run.chips / f_peak, by / run.chips / b_peak
        least.append(max(tf, tb))
        bound["compute" if tf > tb else "memory"] += 1
    run.notes["decode_roofline_bound"] = max(bound, key=bound.get)
    run.notes["decode_active_lanes_mean"] = _mean([len(c) for c in launches])
    return 100.0 * _mean(least) * 1e3 / dms


def mfu(run):
    """Model FLOPs of every prefill and decode token computed while
    tracing, over (the host seconds of the steps that did work x chips x
    peak), in %."""
    rec = run.recorder
    secs = sum(rec.step_s)
    if not secs or not (rec.decodes or rec.prefills):
        return None
    fam = H.family(run.config)
    flops = sum(fam.decode_step(run, c)[0] for c in rec.decodes)
    flops += sum(fam.prefill(run, s, e, head=True) for s, e in rec.prefills)
    return 100.0 * flops / (secs * run.chips * run.peak["bf16_flops"])


def prefix_hit_share(run):
    """Prompt tokens served from shared blocks over prompt tokens of the
    requests admitted in the window (%)."""
    c0, c1 = run.counters
    if not run.admitted_prompt_tokens:
        return None
    hits = (c1["shared_block_hits"] - c0["shared_block_hits"]) \
        * run.config["engine"]["block_size"]
    return 100.0 * hits / run.admitted_prompt_tokens
