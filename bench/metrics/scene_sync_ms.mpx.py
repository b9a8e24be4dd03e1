"""Mean ``scene.sync`` span: the per-stack wait for the device's runs, ms."""

from observe import span_mean_ms


def read(obs):
    return span_mean_ms(obs, "scene.sync")
