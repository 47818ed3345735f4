"""Tokens delivered inside the window / window, client side. Not judged
in a cell below the knee: it is the offered load, and in a window that
opens on an empty system it follows when the long answers happen to
arrive (166 - 212 tokens/s in six runs of the same traffic). It is the
end-to-end metric of a cell above the knee. Layer: client_view."""


def read(run):
    return run.facts["out_tok_per_s"]
