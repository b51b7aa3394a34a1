"""Share of the occupied slot-steps in the window that fed a prompt token
and yielded no output token (the engine's ``serve.prompt_slot_steps``
over it plus ``serve.gen_slot_steps``): the decode steps that prompt
ingestion, one token a step, takes from generation."""


def read(r):
    c = r.window.counts
    if "prompt_slot_steps" not in c:
        return None
    occupied = c["prompt_slot_steps"] + c["gen_slot_steps"]
    return c["prompt_slot_steps"] / occupied if occupied else None
