"""serve.prefill_tokens_per_dispatch: prompt tokens per prefill dispatch
in the window (engine stats prefill_rows / prefill_dispatches)."""


def read(obs):
    w = obs.get("window")
    if not w or not w["prefill_dispatches"]:
        return None
    return w["prefill_rows"] / w["prefill_dispatches"]
