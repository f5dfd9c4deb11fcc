"""entry: host clock around the process's first execution, ``sql(text)`` and ``collect()`` (read,
H2D, plan, compile or compile-cache load)."""


def read(run):
    return run["setup"]["first_query_s"]
