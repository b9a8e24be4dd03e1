"""Device ms a ``jit_fused_analyze*`` launch spends outside its kernel
(cast, layout copy, pad)."""

from stages import prep_ms


def read(obs):
    return prep_ms(obs)
