"""Share of the paged decode kernel's grid that holds live keys: the
mean of the program's `serve.decode_pages` gauge (pages the decode
call's rows hold) over the block-table slots its grid addresses
(`serve.decode_page_slots`: max_batch x pages per row), in percent."""
from bench.metrics import _fold


def read(run):
    pages = _fold.edge("decode_pages")
    slots = _fold.edge("decode_page_slots")
    if pages is None or slots is None or not slots.total_ns:
        return None
    return 100.0 * pages.total_ns / slots.total_ns
