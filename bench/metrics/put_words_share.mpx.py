"""Share of ``engine.put`` spans whose stack went to the device as 32-bit
words (meta ``words=1``), %. None where no put span carries the meta."""


def read(obs):
    flags = [meta["words"] for name, _, _, meta in obs.spans
             if name == "engine.put" and "words" in meta]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
