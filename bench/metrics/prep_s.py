"""Host preparation seconds: the program's own count (NodeTask.prep_seconds)
of reorder, condition check, encodings and every ladder rung's layout."""


def read(r):
    return r.get("prep_s")
