"""Share of the traced window in which no operation ran on the chip, in a
serving cell: what ServeEngine.generate's host loop leaves idle."""


def read(view):
    if not view.has_program("decode_step") and not view.has_program("prefill"):
        return None
    return view.idle_share()
