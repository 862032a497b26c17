"""serve.prefill_share: prefill executables' share of the device's busy
time in the traced window."""

MODULE = "jit_prefill_fn"


def read(obs):
    tr = obs.get("trace")
    if tr is None or "window" not in obs or tr.busy_s <= 0:
        return None
    s, n = tr.module_seconds(MODULE)
    return 100.0 * s / (tr.busy_s * len(tr.devices))
