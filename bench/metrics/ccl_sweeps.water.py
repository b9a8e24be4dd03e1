"""Mean ``sweeps`` meta of the ``engine.fetch`` spans: the band kernel's
raster passes for one tile, summed over its bands (one pass is one walk
down or up a band; the last finds nothing to lower)."""


def read(obs):
    vals = [meta["sweeps"] for name, _, _, meta in obs.spans
            if name == "engine.fetch" and "sweeps" in meta]
    if not vals:
        return None
    return sum(vals) / len(vals)
