"""Programs compiled or loaded inside the window; set-up should leave 0."""


def read(w):
    return float(w.window_programs)
