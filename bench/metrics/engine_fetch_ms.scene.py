"""Mean ``engine.fetch`` span: one scene's result waited for and copied to
the host, ms."""

from observe import span_mean_ms


def read(obs):
    return span_mean_ms(obs, "engine.fetch")
