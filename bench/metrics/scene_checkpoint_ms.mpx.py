"""Mean ``scene.checkpoint`` span: one bulk-job checkpoint written, ms."""

from observe import span_mean_ms


def read(obs):
    return span_mean_ms(obs, "scene.checkpoint")
