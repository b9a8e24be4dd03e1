"""Mean ``scene.read`` span: one stack of strips copied from the granule, ms."""

from observe import span_mean_ms


def read(obs):
    return span_mean_ms(obs, "scene.read")
