"""serve.decode_step_ms: device milliseconds per execution of the decode
step executable (jit_step_fn), from the traced window."""

MODULE = "jit_step_fn"


def read(obs):
    tr = obs.get("trace")
    if tr is None or "window" not in obs:
        return None
    s, n = tr.module_seconds(MODULE)
    return 1e3 * s / n if n else None
