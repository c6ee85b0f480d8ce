"""Share of the traced window in which no operation ran on the device,
on a served cell."""
from bench.readers import idle_share


def read(run):
    return idle_share(run)
