"""Device ms a call spends in the ``jit_ccl_seam_merge`` and
``jit_ccl_relabel`` programs: the seam pairs resolved, then every pixel
mapped to its component's root and re-ranked. Per call, one relabel
program each; averaged over the devices that ran them."""

from trace_reduce import covered, module_base

PROGRAMS = ("jit_ccl_seam_merge", "jit_ccl_relabel")


def read(obs):
    if obs.trace is None:
        return None
    per = []
    for mods in obs.trace.modules.values():
        runs = [(a, b) for name, a, b in mods
                if module_base(name).startswith(PROGRAMS)]
        calls = sum(module_base(name).startswith(PROGRAMS[1])
                    for name, _, _ in mods)
        if calls:
            per.append(covered(runs) / calls)
    if not per:
        return None
    return 1e3 * sum(per) / len(per)
