"""The flash-attention kernels' share of their roofline in the traced
training steps: the least time the chip could take for the attention
the steps needed (the larger of ops / peak FLOP/s and bytes / peak
bytes/s, `harness/opcount.py`) over the device time of the kernels'
events (forward, dQ, dK/dV). The program gives its `pallas_call`s no
name, so the trace shows them as numbered Mosaic custom calls
("%jvp__.59 = ... custom-call(...), custom_call_target=
"tpu_custom_call""); they are told from the fused-xent calls by their
[batch * heads, seq, head_dim] operands."""
from perfbench.harness import opcount, xplane

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"


def kernels(batch_heads, seq, head_dim):
    """Regex of the flash-attention custom calls at these shapes."""
    return (r'\[%d,%d,%d\].*custom_call_target="tpu_custom_call"'
            % (batch_heads, seq, head_dim))


def read(run):
    if run.device_trace is None or "traced_steps" not in run.samples:
        return None
    k = run.config["builder"]["kwargs"]
    seq = run.workload["items_per_example"]
    head_dim = k["d_model"] // k["num_heads"]
    spent = xplane.kernel_seconds(
        run.device_trace,
        kernels(run.workload["batch"] * k["num_heads"], seq, head_dim),
        *run.trace_window_ns)
    if not spent:
        return None
    ops, nbytes = opcount.attention_fwd_bwd(
        run.workload["batch"], k["num_heads"], seq, head_dim, itemsize=2)
    least, bound = opcount.roofline_seconds(ops, nbytes, run.peaks)
    calls = run.samples["traced_steps"] * k["num_layers"]
    run.notes["attn_roofline"] = (
        f"{bound}-bound; {calls} layer-steps need {calls * least:.4f} s at "
        f"the peak, the kernels took {spent:.4f} s")
    return 100.0 * calls * least / spent
