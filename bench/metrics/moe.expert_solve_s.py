"""Host seconds per block in the ``ptq.solve`` spans of expert groups (``experts=<E>``), from obs.record()."""


def read(run):
    return run.info.get("moe.expert_solve_s")
