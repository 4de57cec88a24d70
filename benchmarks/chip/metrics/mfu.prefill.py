"""FLOPs a prefill call requires (causal triangle, last position's logits),
over its device time times the peak bf16 FLOP/s (%)."""


def read(view):
    ms = view.program_ms("prefill")
    need = view.required.get("prefill")
    if ms is None or not need:
        return None
    return 100.0 * need["flops"] / (ms / 1e3 * view.peak_flops)
