"""Requests answered within the window, over the window's length."""


def read(run):
    return run.answered_in_window / run.window_s
