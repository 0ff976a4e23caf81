def read(rec):
    """Output tokens emitted inside the window over the window."""
    if "out_tokens" not in rec:
        return None
    return rec["out_tokens"] / rec["window_s"]
