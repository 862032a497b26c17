"""serve.paged_attn_roofline: the paged-attention kernel's share of its
roofline in decode steps: the least time its work could take (live KV
rows read once, QK and PV over live keys; bench.harness.work) at the
chip's peaks, over the device time of the kernel's ops inside the decode
executable, from the traced window."""

KERNEL = "paged_attention"
MODULE = "jit_step_fn"


def read(obs):
    tr, work = obs.get("trace"), obs.get("work")
    if tr is None or not work or not work["attn_min_s"]:
        return None
    t = tr.kernel_s(KERNEL, module=MODULE)
    return 100.0 * work["attn_min_s"] / t if t > 0 else None
