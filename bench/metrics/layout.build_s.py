"""Host seconds the program spends on a new graph's first query beyond a
repeat of it, less compile seconds: layout build and upload, validation,
graph statistics."""


def read(run):
    return run.setup.get("layout_s")
