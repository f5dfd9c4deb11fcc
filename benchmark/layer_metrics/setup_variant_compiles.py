"""stage pipeline: set-up's executions after the first query's two that
compiled anything, in a mix of several texts of one query shape: the first
submission of every other substitution set.  0 where a literal is bound at
execution; one a set where it is baked into the program.  Nothing to read
in a mix of one query."""


def read(run):
    if len(run["mix"]["queries"]) < 2:
        return None
    return sum(1 for e in run["setup"]["executions"][2:]
               if e["counters"].get("compileCount", 0))
