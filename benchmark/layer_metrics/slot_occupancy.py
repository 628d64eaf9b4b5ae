"""serving engine (serving/engine.py, blocks.py, prefix_cache.py):
mean share of the decoder's slots that decoded, over the engine's
decode steps inside the window (``ServingRecorder.steps``)."""


def read(facts):
    steps = facts.get("engine_steps")
    if not steps:
        return None
    mean = sum(s["active_slots"] for s in steps) / len(steps)
    return mean / facts["max_slots"]
