"""Backend compile seconds during set-up, persistent-cache retrievals
included (JAX monitoring events)."""


def read(run):
    return run.setup.get("compile_s")
