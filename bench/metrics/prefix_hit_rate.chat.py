"""Share of prompt tokens served from the radix prefix cache
(serve/prefix.py) over the window: the engine's ``prefix_hit_tokens``
over ``prompt_tokens_total``."""


def read(run):
    total = run.counters.get("prompt_tokens_total", 0)
    if not total:
        return None
    return 100.0 * run.counters["prefix_hit_tokens"] / total
