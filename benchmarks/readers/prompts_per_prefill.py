"""Prompts prefilled in the traced span for each run of the prefill program
there: how many waiting prompts of one pad bucket the engine admits as one
wave. It rises when pad buckets get coarser or admission waits for company;
traffic whose prompt lengths are snapped to a few values reads higher than
lengths spread over every 16-token pad would."""
from benchmarks.readers.prefill_roofline import span_prompt_lens


def read(run: dict, program: str):
    trace, lens = run.get("trace"), span_prompt_lens(run)
    if not trace or not lens or not trace["programs"].get(program, {}).get("count"):
        return None
    return len(lens) / trace["programs"][program]["count"]
