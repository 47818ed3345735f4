"""Share of set-up's compile requests that the persistent cache
answered (100 from the second run of a cell in a checkout on). Layer:
compile_cache."""


def read(run):
    setup = run.compile_counts["setup"]
    if not setup["requests"]:
        return None
    return 100.0 * setup["hits"] / setup["requests"]
