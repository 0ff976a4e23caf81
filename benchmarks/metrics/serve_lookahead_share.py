def read(rec):
    """Decode programs enqueued while the previous one was still unread
    (`stats["lookahead_steps"]`), over the window's decode steps: how
    often the chip had its next step queued before the host looked at
    the last one. None where the program keeps no such counter."""
    stats = rec.get("stats") or {}
    if "lookahead_steps" not in stats or not rec.get("decode_steps"):
        return None
    return 100.0 * stats["lookahead_steps"] / rec["decode_steps"]
