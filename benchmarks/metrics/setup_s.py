def read(rec):
    """Process start to the first measured step or request."""
    return rec["setup_s"]
