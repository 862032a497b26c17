"""serve.mfu: model operations of every token prefilled and decoded in
the traced window (counted from live lengths: matrix products, causal
attention, logits where computed; padding rows excluded) over the
window's length times the chip's peak bf16 rate, per chip."""


def read(obs):
    tr, work = obs.get("trace"), obs.get("work")
    if tr is None or not work or tr.window_s <= 0:
        return None
    flops = work["prefill_flops"] + work["decode_flops"]
    if not flops:
        return None
    peak = obs["peaks"]["bf16_flops_per_s"] * obs["chips"]
    return 100.0 * flops / (tr.window_s * peak)
