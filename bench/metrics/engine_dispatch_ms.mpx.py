"""Mean ``engine.dispatch`` span: one stack's backend run and post-ops
issued, ms."""

from observe import span_mean_ms


def read(obs):
    return span_mean_ms(obs, "engine.dispatch")
