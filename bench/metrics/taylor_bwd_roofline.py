"""Share of its roofline that the Pallas backward pair of Taylor attention
(the dq kernel and the dk/dv kernel) reaches, in %, from the trace.  One
pair is one layer over the whole batch; its required work is
``work.taylor_bwd_flops`` and ``taylor_bwd_bytes`` per sequence, times the
batch, over both kernels' summed time.  Bound by operations.

The program does not name the backward kernels (their calls take the name
of the enclosing remat, ``checkpoint``): they are taken as every Pallas
call (``tpu_custom_call``) of the step but the forward kernel.  Where
their count is not two per layer and traced step, some other Pallas call
is among them, and the metric is left out rather than misread."""

from bench import work

FORWARD = "%taylor_attention_kernel"
PALLAS = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None or "batch" not in run.data:
        return None
    all_s, calls = run.trace.time_of([PALLAS])
    fwd_s, fwd_calls = run.trace.time_of([FORWARD], also=PALLAS)
    w, b, n = work.Widths.of(run.data["cfg"]), run.data["batch"], run.data["seq"]
    pairs = w.layers * run.data.get("traced_steps", 0)
    if not pairs or calls - fwd_calls != 2 * pairs:
        return None
    share, _ = work.roofline_share(pairs * b * work.taylor_bwd_flops(w, n),
                                   pairs * b * work.taylor_bwd_bytes(w, n), all_s - fwd_s, run.peak)
    return share
