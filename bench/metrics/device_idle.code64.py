"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices."""
from readers import idle_pct


def read(run):
    return idle_pct(run)
