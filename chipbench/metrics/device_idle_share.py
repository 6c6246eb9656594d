"""device: share of the traced window in which no operation ran on the
chip, averaged over the chips used.  Moves ``output_tok_s``."""


def read(run):
    window = run.window_s
    return 100.0 * (1.0 - run.busy_s / window) if window else None
