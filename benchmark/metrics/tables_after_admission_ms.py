"""The ``serve.tables`` region of a pass that admitted a request: the host's
edit of the page table and the lengths and their copies to the device, made
right after a prefill program was enqueued (a pipeline start that admitted
plans twice inside one region).  Read from the span buffer: the mean
duration of the ``serve.tables`` spans whose ``iter`` is that of a
``serve.iteration`` span with a non-empty ``admitted``, from the first
admission of the window's requests on (``admit_iter`` of their
``serving.request`` spans, taken as ``itl_p99_ms`` takes them).  The buffer
keeps the last 20000 events, so in a cell of short passes it is the window's
last stretch.  A steady pass's takes one to two milliseconds
(``serve_host_ms`` holds it), and so has this one wherever it was read; it
stands for the day a traced run shows the device idle under it again.  A
program whose spans carry no ``admitted`` reads nothing."""
LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(run, events=None):
    reqs = getattr(run, "requests", None)
    if not reqs:
        return None
    if events is None:
        from horovod_tpu import trace

        events = trace.export_events()
    named = {"serving.request": [], "serve.iteration": [],
             "serve.tables": []}
    for e in events:
        if e.get("name") in named:
            named[e["name"]].append(e)
    since = [(e.get("args") or {}).get("admit_iter")
             for e in named["serving.request"][-len(reqs):]]
    if not since or None in since:
        return None
    admitting = {e["args"]["iter"] for e in named["serve.iteration"]
                 if e["args"].get("admitted")
                 and e["args"]["iter"] >= min(since)}
    took = [e["dur"] for e in named["serve.tables"]
            if e["args"]["iter"] in admitting]
    return sum(took) / len(took) / 1e3 if took else None
