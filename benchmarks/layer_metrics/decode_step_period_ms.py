"""Window / ``decode_steps`` (the engine's counter): how often every
live stream gets a token, prefills and host work included. Layer: engine
(``serving/decode.py``)."""


def read(run):
    steps = run.facts["engine"]["decode_steps"]
    if not steps:
        return None
    return (run.window[1] - run.window[0]) / steps * 1e3
