"""Update kernel layer: the fused mix+SGD Pallas kernel
(kernels/fused_update.py) against its roofline. The bound is bytes: the
least time of the kernel's HBM streams at peak bandwidth (bench/flops.py:
update_bytes, from the real bucket sizes and dtypes; the partner stream only
when the gossip mix is on), over the device time of the kernel's ops in the
trace, per step and averaged over chips, in percent."""
import re

from bench import flops, tracing

# The step's Mosaic kernels are the fused update's, one or two per bucket
# (an aligned body and a ragged tail). Their pallas_calls carry no name, so
# XLA:TPU names them "%_unknown_.<n> = (...) custom-call(...)"; a name
# given later (fused_sgd...) is matched too.
KERNEL = re.compile(r"^%(_unknown_|\S*(fused|sgd)\S*)[.\d]* = .*custom-call\(")


def read(rec):
    tr, peaks = rec["trace"], rec["peaks"]
    if tr is None or not peaks:
        return None
    kernel_s = tracing.mean_op_time_s(tr, lambda op: bool(KERNEL.match(op.name)))
    if kernel_s <= 0:
        return None
    u = rec["update"]
    least_s = flops.update_bytes(
        u["bucket_elems"], param_bytes=u["param_bytes"],
        grad_bytes=u["grad_bytes"], moment_bytes=u["moment_bytes"],
        partner=u["partner"]) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s * rec["steps"] / kernel_s
