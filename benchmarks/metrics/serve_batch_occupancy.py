def read(rec):
    """Decoded tokens over decode steps times the batch the decode
    program is compiled for."""
    if not rec.get("decode_steps"):
        return None
    return 100.0 * rec["stats"]["decode_tokens"] / \
        (rec["decode_steps"] * rec["max_batch_size"])
