"""Arithmetic shared by the device-trace metric readers: which programs
and ops belong to which layer, how many tokens and token steps the
traced window covered, and the FLOP and byte counts of ``flops.py``."""
from __future__ import annotations

import re
import sys
from typing import Optional

import devtrace
import flops as F

FUSION_KERNEL = re.compile(r"^(fused_probs_masked|fuse_logits)")


def _note(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def traced(run) -> bool:
    return run.dops is not None and run.trace is not None


def calls(run):
    """(host time, entry point) of every call made in the traced
    window."""
    on, off = run.trace.on, run.trace.off
    return [c for c in run.programs.log if on <= c[0] <= off]


def category_seconds(run, category: str) -> Optional[float]:
    """Device seconds per chip of a layer's programs in the window.
    Programs that ran but are missing from the trace fail the metric."""
    if not traced(run):
        return None
    entries = run.programs.table[category]
    t = run.dops.entry_seconds(entries)
    if not t:
        if any(c[1] in entries for c in calls(run)):
            _note(f"{category} programs ran in the traced window but none "
                  "of their executions is in the trace")
        return None
    return t


def prefill_tokens(run) -> int:
    """Prompt tokens of the requests admitted in the traced window (the
    driver's records: counting them from the prefill calls' arguments
    would make the host wait for the device)."""
    on, off = run.trace.on, run.trace.off
    return sum(r.req.prompt_tokens for r in run.records.values()
               if r.admitted is not None and on <= r.admitted <= off)


def macro_calls(run, entries=None):
    entries = entries or run.programs.table["decode"]
    return [c for c in calls(run) if c[1] in entries]


def token_steps(run) -> int:
    """K per boundary of the traced window that dispatched a macro-step
    (the lanes dispatched at one boundary advance together)."""
    if not traced(run):
        return 0
    k = run.cfg["engine"]["macro_k"]
    times = [c[0] for c in macro_calls(run)]
    n = sum(1 for b in run.trace.boundaries()
            if any(b.start <= t <= b.end for t in times))
    return k * n


def fusion_kernel_share(run) -> Optional[float]:
    """Least time of the fusion kernel's executions in the window over
    their device time (a fraction).  Each execution's bytes come from
    the shapes in its op text, per memory space (XLA may hand the kernel
    operands it keeps in VMEM); its operations from its result's
    elements (``flops.FUSION_FLOPS_PER_ELEMENT`` each)."""
    if not traced(run):
        return None
    ops = [o for o in run.dops.ops_of(["macro_cloud"])
           if devtrace.KERNEL.search(o.name)
           and FUSION_KERNEL.match(devtrace.short(o.name))]
    if not ops:
        if macro_calls(run, ["macro_cloud"]):
            _note("the cloud macro-step ran but no fusion-kernel op is in "
                  "the trace")
        return None
    least = 0.0
    for o in ops:
        traffic = F.op_traffic(o.name)
        # the kernel writes one float32 per element it computes
        written = sum(b for (kind, _), b in traffic.items()
                      if kind == "write")
        flops = F.FUSION_FLOPS_PER_ELEMENT * written / 4
        least += F.op_least_seconds(flops, traffic, run.peak)[0]
    return least / (sum(o.dur for o in ops) * 1e-9)


def decode_mfu(run) -> Optional[float]:
    """Percent of the bf16 peak the decode programs reached on the
    tokens the host received at the traced window's boundaries."""
    t = category_seconds(run, "decode")
    if not t:
        return None
    cfg = run.cfg
    total = 0
    for b in run.trace.boundaries():
        for rid, n0, n1, private in b.emitted:
            r = run.records.get(rid)
            if r is None:
                continue
            for i in range(n0, n1):
                ctx = r.req.prompt_tokens + i
                total += F.token_flops(cfg["slm"], ctx)
                if not private:
                    total += F.token_flops(cfg["llm"], ctx)
    if not total:
        return None
    return 100.0 * total / (t * run.chips * run.peak["bf16_flops_per_s"])
