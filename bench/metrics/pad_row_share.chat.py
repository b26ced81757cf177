"""Share of the rows the engine's launches computed that were padding.

Read from the program's ``engine.launch.*`` spans in the traced window
(``bench/spans.py``): each carries its launch's ``rows`` (prompt tokens
and decoded slots) and ``pad_rows`` (chunk padding, and decode rows of
slots that were not decoding). Percent of their sum.
"""

from bench import spans


def read(r):
    s = spans.of(r)
    return None if s is None else s.pad_row_share()
