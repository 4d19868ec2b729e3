"""Collective layer: device time a step under ``anat/.../select`` or
``.../stage`` that lies in no kernel and in no sub-scope with a metric of
its own (``<phase>_<sub>_ms``): what those metrics do not account for.
The sub-scopes that read nothing today (``select/threshold``,
``select/feedback``, ``stage/repartition``: every traced step is a
predicted one) are in here, so that one of them growing shows."""
from benchlib import progspans


def read(ctx):
    ms = progspans.sub_scope_ms(ctx)
    if ms is None:
        return None
    return sum(v for k, v in ms.items() if not k.startswith("kernel:")
               and not progspans.has_reader(k))
