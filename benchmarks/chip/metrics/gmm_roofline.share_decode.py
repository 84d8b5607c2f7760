"""The held experts' grouped matmuls in the decode step on the first chip,
against their roofline: the least time of their required work
(``work_share.gmm_work``: each touched expert's weights once, the rows in
and out, the filled rows' flops, at the peaks) over their device time
per run of the decode-step program.

Their device time is that of the ``%gmm`` ops and of the ops that make
their weight operand (the last).  XLA stages a layer's held weights
(8 x 4096 x 1536, 100 MB) into on-chip memory with a fusion of its own
before the kernel, so the kernel alone never reads them from HBM and
its time leaves out that part of the work."""
import re

from benchmarks.chip import scopes, work, work_share, xplane

KERNEL = "%gmm"
CALL = re.compile(r"^\s*(%[\w.\-]+) = .*?\bcustom-call\((.*?)\), "
                  r"custom_call_target=", re.M)
COMMENT = re.compile(r"/\*.*?\*/")


def kernel_ops(text: str) -> set:
    """The ``%gmm`` custom calls of a compiled program's text and the ops
    that make their weight operands."""
    ops = set()
    for name, args in CALL.findall(text):
        if name.split(".")[0] == KERNEL:
            ops.add(name)
            ops.add(COMMENT.sub("", args).split(",")[-1].strip())
    return ops


def read(ctx):
    rec = ctx["records"]
    module = rec.get("programs", {}).get("decode")
    text = scopes.live_texts([module]).get(module) if module else None
    if text is None:
        return None
    ops = kernel_ops(text)
    lo, hi = ctx["window"]
    runs = scopes.run_intervals(ctx["trace"], ctx["device"], module, lo, hi)
    spent = xplane.total(xplane.union(
        (s, e) for name, s, e in scopes.ops_in_runs(ctx["trace"],
                                                    ctx["device"], runs)
        if scopes.op_key(name) in ops))
    if not runs or spent <= 0:
        return None
    w = work_share.gmm_work(ctx["config"], rec["batch"])
    return 100.0 * work.least_time_s(w["flops"], w["bytes"],
                                     ctx["peaks"]) * len(runs) / spent
