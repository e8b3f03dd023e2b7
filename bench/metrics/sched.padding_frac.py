"""Share of the padded prefill work that is padding: 1 - real prompt
tokens / (pad_to x rows), over the batches whose first chunk the
window ran (the scheduler's bucketing sets pad_to)."""


def read(run):
    first = [c for c in run.calls("prefill") if c.first_chunk]
    padded = sum(c.pad_to * c.rows for c in first)
    if not padded:
        return None
    return 1.0 - sum(c.prompt_tokens for c in first) / padded
