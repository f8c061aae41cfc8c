"""Share of the traced window, in %, in which the device ran nothing and no
host annotation with the given prefix was open: idle time the named host work
does not explain (for ``p2pfl:`` — the protocol's clocks, votes and waits)."""

from benchmark import trace_reduce


def read(context, *, prefix: str):
    return 100.0 * trace_reduce.idle_outside(context["trace"], prefix)
