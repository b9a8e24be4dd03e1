"""Mean ``engine.put`` span: one scene's host array made a device array, ms."""

from observe import span_mean_ms


def read(obs):
    return span_mean_ms(obs, "engine.put")
