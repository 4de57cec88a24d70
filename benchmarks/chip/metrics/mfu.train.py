"""FLOPs one chip's share of the train step requires, over the step's device
time times the chip's peak bf16 FLOP/s (%)."""


def read(view):
    ms = view.program_ms("train_step")
    need = view.required.get("train_step")
    if ms is None or not need:
        return None
    return 100.0 * need["flops"] / (ms / 1e3 * view.peak_flops)
