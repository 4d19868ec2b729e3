"""Entry layer: median host time of the jitted step's call inside
``Trainer.train_step``, the program's ``oktopk/dispatch`` span."""
from benchlib import progspans


def read(ctx):
    v = progspans.view(ctx)
    return None if v is None else v.median_ms(progspans.DISPATCH)
