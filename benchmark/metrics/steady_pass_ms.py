"""One decode pass alone: the mean ``serve.iteration`` of a STEADY pass of the
serve loop (an iteration was in flight, no prefill was enqueued, the next
iteration was launched): admit, page ``ensure``, tables, launch, the wait for
the iteration in flight and its sampling.  The program names every pass where
it makes it and keeps one histogram a kind (``serving.pass_seconds.<kind>``);
``decode_iter_ms`` reads the one histogram that holds every kind.  A program
without the family reads nothing."""
LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"

KINDS = ("start", "admission", "steady", "retire", "sync")


def family(run, prefix, kinds=KINDS):
    """``(seconds, observations)`` the window added to the named members
    of one of the program's two families of histograms a pass kind."""
    return (sum(run.counter_delta(prefix + k, "sum") for k in kinds),
            sum(run.counter_delta(prefix + k, "count") for k in kinds))


def mean_ms(run, prefix, *kinds):
    seconds, n = family(run, prefix, kinds)
    return 1e3 * seconds / n if n else None


def read(run):
    return mean_ms(run, "serving.pass_seconds.", "steady")
