"""Device time per run of the prefill program (ms)."""


def read(view):
    return view.program_ms("prefill")
