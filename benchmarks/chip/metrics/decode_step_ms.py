"""Device time per run of the decode step program (ms)."""


def read(view):
    return view.program_ms("decode_step")
