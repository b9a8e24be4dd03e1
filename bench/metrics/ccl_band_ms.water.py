"""Device ms of one launch of the ``ccl_band_labels`` kernel: every band
of one tile labelled to its own fixpoint."""

from stages import kernel_ms


def read(obs):
    return kernel_ms(obs, "ccl_band_labels")
