"""Share of the traced window in which no operation ran on the chips (the
mean over them), in a training cell: what the host loop leaves idle."""


def read(view):
    if not view.has_program("train_step"):
        return None
    return view.idle_share()
