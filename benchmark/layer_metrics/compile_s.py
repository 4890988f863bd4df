"""compile_s (entry points): the sum of JAX's backend-compile events over
the whole process, as ``chip_smoke.py`` counts them.  A program loaded from
the persistent cache reports its load time under the same event."""


def read(view):
    return view.cell.compiles.seconds()
