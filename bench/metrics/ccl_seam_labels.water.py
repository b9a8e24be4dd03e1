"""Mean ``seam_labels`` meta of the ``engine.fetch`` spans: the distinct
nonzero labels on one tile's seam rows, which the seam merge numbers into
its table (at most 2 x seams x the padded width: 68,096 for a MOD44W
tile)."""


def read(obs):
    vals = [meta["seam_labels"] for name, _, _, meta in obs.spans
            if name == "engine.fetch" and "seam_labels" in meta]
    if not vals:
        return None
    return sum(vals) / len(vals)
