"""Share of the prompt tokens admitted in the window that the prefix
cache served (``serve/prefix.py``), in %: the engine's exact counters,
``prefill_tokens_skipped`` over ``prompt_tokens``, differenced over the
window."""


def read(run):
    prompt = run.stats1["prompt_tokens"] - run.stats0["prompt_tokens"]
    hit = (run.stats1["prefill_tokens_skipped"]
           - run.stats0["prefill_tokens_skipped"])
    return 100.0 * hit / prompt if prompt > 0 else None
