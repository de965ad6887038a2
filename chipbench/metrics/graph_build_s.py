"""Host-clock seconds of the program's device layout build and upload
(``WalkEngine.build`` from the CSR, until the arrays are on the device), in
set-up (data layer). Moves setup_s."""


def read(ctx):
    return ctx["info"].get("graph_build_s")
