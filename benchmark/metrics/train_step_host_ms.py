"""Entry layer: median host time of one ``Trainer.train_step`` call, the
program's own ``oktopk/step`` span (key split, argument handling, input
copy, enqueue; no wait for the device), from the traced window."""
from benchlib import progspans


def read(ctx):
    v = progspans.view(ctx)
    return None if v is None else v.median_ms(progspans.STEP)
