"""1 - (union of device-op intervals) / window over the traced steps, mean over devices. Waiting
inside a collective counts as busy; ``coll_exposed_ms`` says how much.
"""


def read(trace, notes):
    return trace and trace["idle_pct"]
