"""KV pool / radix tree: prompt tokens served from cached prefix blocks
over prompt tokens admitted in the window, in percent."""


def read(run):
    d = run.counter_delta("window")
    if not d["prompt_tokens"]:
        return None
    return 100.0 * d["prefix_hit_tokens"] / d["prompt_tokens"]
