"""stage pipeline: programs compiled by set-up's executions after the first,
in a mix whose set-up submits the query's text before it prepares the held
statement (``text_submissions_in_setup``).  The same text has then already
compiled, so any compile here is the plan cache losing its entry (PERF.md,
Open questions 1): 2 today, 0 once a resubmitted text finds its programs.
Nothing to read where set-up submits no text."""


def read(run):
    if not run["mix"].get("text_submissions_in_setup"):
        return None
    return sum(e["counters"].get("compileCount", 0)
               for e in run["setup"]["executions"][1:])
