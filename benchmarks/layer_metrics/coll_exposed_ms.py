"""The part of ``coll_ms`` during which no compute op runs on that device, ms a step."""


def read(trace, notes):
    return trace["coll_exposed_ms_per_step"] if trace and trace["coll_ms_per_step"] else None
