"""Share of the traced window in which no op ran on the device, %."""

from observe import idle_pct


def read(obs):
    return idle_pct(obs)
