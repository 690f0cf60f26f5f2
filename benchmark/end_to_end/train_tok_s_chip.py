"""Tokens of the steps finished inside the window, over window and chips; each
step counted is ended by a device barrier."""


def read(trace, stats, record):
    return record["tokens_in_window"] / record["window_s"] / record["chips"]
