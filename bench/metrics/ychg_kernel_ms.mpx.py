"""Device ms of one launch of the ``ychg_fused*`` kernel."""

from stages import kernel_ms


def read(obs):
    return kernel_ms(obs)
