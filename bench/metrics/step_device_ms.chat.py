"""Device milliseconds of one serving round's program (the engine's
``mixed_step``, whose jitted module is named ``jit_run``), mean over the
executions in the traced window."""
from readers import module_ms

MODULE = "jit_run"


def read(run):
    return module_ms(run, MODULE)
