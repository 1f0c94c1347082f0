"""Share of the served chunks' rows that carried no read
(``ServeDriver.n_pad_rows`` over ``n_chunks`` x chunk, in the window)."""


def read(ctx):
    ex = ctx["window"].extra
    if not ex.get("n_chunks"):
        return None
    return 100.0 * ex["n_pad_rows"] / (ex["n_chunks"] * ex["chunk"])
