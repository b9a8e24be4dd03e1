"""Share of the window in which the device idles and the host is in no
``engine.*`` / ``scene.*`` stage, %."""

from stages import idle_outside_stages_pct


def read(obs):
    return idle_outside_stages_pct(obs)
