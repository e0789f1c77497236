"""Compile requests inside the window (CompileWatch delta). Expect 0: a
compile stalls every lane and explains a bad tail before any layer does."""


def read(run):
    return run.window["compiles"]["total"]
