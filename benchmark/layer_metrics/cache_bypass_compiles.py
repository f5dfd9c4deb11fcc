"""stage pipeline: programs compiled by a donating dispatch, which bypass the
persistent compile cache and compile again in every process."""


def read(run):
    return run["setup"]["cache"]["bypassedDonating"]
