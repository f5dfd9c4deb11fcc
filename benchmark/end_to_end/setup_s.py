"""Process start to window start: imports, data, session, read, H2D, compile
or compile-cache load, the mix's warm-up executions (host clock)."""


def read(run):
    return run["setup"]["setup_s"]
