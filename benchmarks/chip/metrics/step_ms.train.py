"""Device time per run of the train step program, on the busiest chip (ms)."""


def read(view):
    return view.program_ms("train_step")
