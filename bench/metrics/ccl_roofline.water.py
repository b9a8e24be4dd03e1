"""Share of the HBM roofline reached by the ``ccl`` programs, %.

The least bytes the window's ``ccl`` calls must move (``floor_bytes``)
at peak bandwidth, over the device time of every op inside the
``jit_ccl_*`` programs (band labels, seam merge, relabel; the
``ingest_unpack`` of the words is not counted)."""

PROGRAM = "jit_ccl_"


def floor_bytes(spans) -> int:
    """5 B a pixel, the mask in as uint8 and the labels out as int32, and
    4 B an image, its ``n_components``, for the ``engine.dispatch`` spans
    of op ``ccl`` (each call of this cell is one tile)."""
    return sum(5 * meta["px"] + 4 for name, _, _, meta in spans
               if name == "engine.dispatch" and meta.get("op") == "ccl")


def read(obs):
    if obs.trace is None:
        return None
    need = floor_bytes(obs.spans)
    device_s = obs.trace.module_s(PROGRAM)
    if need <= 0 or device_s <= 0:
        return None
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / device_s
