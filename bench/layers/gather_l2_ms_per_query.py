"""Kernels (`kernels/gather_l2`): device time of the fused gather +
distance kernel per query answered in the window.  The kernel carries
no name into the trace; it is the Pallas call (`tpu_custom_call`) that
takes the K candidate ids (`s32[K]`) and returns their K distances
(`f32[1,K,1]`), counted inside the host spans of the search calls that
lie wholly inside the traced part of the window, so the insert path's
own calls of the kernel are left out.  If a change to the kernel's signature
stops the pattern from matching, the metric goes missing from the
traced run's line, which the run reports as an error."""

from harness import trace

PATTERN = (r'^%\S+ = f32\[1,(\d+),1\]\S* custom-call\(s32\[\1\]\S* '
           r'.*custom_call_target="tpu_custom_call"')


def read(run):
    if run.events is None:
        return None
    ns = trace.kernel_ns(run.events, PATTERN, run.window_ns,
                         within=trace.whole_spans(run.events, "bench.search",
                                                  run.window_ns))
    n = sum(len(c.keys) for c in run.log_traced if c.kind == "search")
    return ns / 1e6 / n if ns > 0 and n else None
