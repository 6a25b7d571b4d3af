"""Host seconds per block in the ``ptq.emit`` spans of expert leaves (``experts=<E>``), from obs.record()."""


def read(run):
    return run.info.get("moe.expert_emit_s")
