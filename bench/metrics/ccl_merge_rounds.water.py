"""Mean ``merge_rounds`` meta of the ``engine.fetch`` spans: the rounds of
hooking and pointer jumping that resolved one tile's seam pairs."""


def read(obs):
    vals = [meta["merge_rounds"] for name, _, _, meta in obs.spans
            if name == "engine.fetch" and "merge_rounds" in meta]
    if not vals:
        return None
    return sum(vals) / len(vals)
