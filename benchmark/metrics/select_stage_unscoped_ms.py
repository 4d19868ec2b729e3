"""Collective layer: device time a step under ``anat/.../select`` or
``.../stage`` that lies in no kernel and in no sub-scope with a metric of
its own (``<phase>_<sub>_ms``): what those metrics do not account for.
The sub-scopes that read nothing today (``select/threshold``,
``select/feedback``, ``stage/repartition``: every traced step is a
predicted one) are in here, so that one of them growing shows. Another
phase's sub-scopes (the model's, under ``fwd_bwd``) are not selection's
to account for: only labels of the two phases are summed."""
from benchlib import progspans

PHASES = ("select_", "stage_")


def read(ctx):
    ms = progspans.sub_scope_ms(ctx)
    if ms is None:
        return None
    return sum(v for k, v in ms.items() if k.startswith(PHASES)
               and not progspans.has_reader(k))
