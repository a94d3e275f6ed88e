"""Share of the suffix prefill's rows x positions that held suffix
tokens, over the window's prefill spans: suffix tokens over ``rows`` x
``padded_len``.  Empty rows and padding past each suffix are the waste.
A program whose prefill spans carry no ``rows`` gives nothing."""


def read(rec):
    spans = [s for s in rec.spans
             if s["cat"] == "prefill" and "rows" in s["args"]]
    room = sum(s["args"]["rows"] * s["args"]["padded_len"] for s in spans)
    if not room:
        return None
    return 100.0 * sum(s["args"]["suffix_tokens"] for s in spans) / room
