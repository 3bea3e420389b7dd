"""Share of its roofline that the Pallas forward kernel of Taylor
attention reaches, in %, from the trace.  Each of the kernel's events is
one layer over the whole batch; its required work is
``work.taylor_fwd_flops`` and ``taylor_fwd_bytes`` at the model's own
head width, per sequence, times the batch.  Bound by operations.

The kernel is the Pallas call (``tpu_custom_call``) that the program's
``taylor_attention_kernel`` wrapper names."""

from bench import work

KERNEL = "%taylor_attention_kernel"
PALLAS = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None or "batch" not in run.data:
        return None
    seconds, count = run.trace.time_of([KERNEL], also=PALLAS)
    if not count:
        return None
    w, b, n = work.Widths.of(run.data["cfg"]), run.data["batch"], run.data["seq"]
    share, _ = work.roofline_share(count * b * work.taylor_fwd_flops(w, n),
                                   count * b * work.taylor_fwd_bytes(w, n), seconds, run.peak)
    return share
