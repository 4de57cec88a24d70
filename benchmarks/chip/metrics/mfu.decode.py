"""The whole decode step's roofline share: the least time the chip could take
(the larger of required FLOPs over peak FLOP/s and required bytes over peak
HBM bandwidth; for decode the bytes bound it), over the step's device time
(%).  Required bytes are the weights plus K and V at the filled positions."""


def read(view):
    ms = view.program_ms("decode_step")
    need = view.required.get("decode_step")
    if ms is None or not need:
        return None
    least = max(need["flops"] / view.peak_flops, need["bytes"] / view.peak_bytes)
    return 100.0 * least / (ms / 1e3)
