"""What a bucket's padding costs: rows the admission prefills' programs
computed over the real prompt tokens they ran (``serving.prefill_rows``
over ``serving.prefill_tokens``).  A program that computes its whole
bucket reads the mix's padding (1.29 under ``serve-longdoc-32``: half the
prompts ride the 16384 bucket); one that walks a prompt a stretch of 2048
rows at a time as far as it reaches (``models/latent_moe.py``
``walked_prefill``) reads the last stretch's alone.  ``None`` where the
program has no such counter."""
LAYER = "serving"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    rows = run.counter_delta("serving.prefill_rows")
    tokens = run.counter_delta("serving.prefill_tokens")
    if not rows or not tokens:
        return None
    return rows / tokens
