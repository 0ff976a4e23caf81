def read(rec):
    """Tokens of the steps completed in the window, over the window, over
    the cell's chips."""
    if "tokens_per_step" not in rec:
        return None
    return rec["attempted"] * rec["tokens_per_step"] / rec["window_s"] / \
        rec["chips"]
