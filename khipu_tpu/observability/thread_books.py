"""Per-thread books for the read path's always-on counters.

A book is a short list of numbers that ONE thread adds to; the books
live in a dict keyed by ``threading.get_ident()``. A reader finds its
own book with one dict look-up and adds without a lock: nothing else
writes that entry (inserting a fresh one is a single dict store, atomic
under the GIL). A collector sums the columns over a copy of the dict's
values, and a thread that wants what *it* paid reads its own book by
ident before and after a stretch of work (``sync/replay.py`` does, per
block). An ident the interpreter hands to a later thread carries on in
the same book, which keeps the totals monotonic.
"""

from __future__ import annotations

from threading import get_ident
from typing import Dict, List, Optional


class ThreadBooks:
    __slots__ = ("_books", "_zero")

    def __init__(self, *zero):
        self._zero = zero  # one 0 or 0.0 per column
        self._books: Dict[int, List] = {}

    def mine(self) -> List:
        """The calling thread's book, made on its first use."""
        ident = get_ident()
        book = self._books.get(ident)
        if book is None:
            book = self._books[ident] = list(self._zero)
        return book

    def of(self, ident: Optional[int] = None) -> List:
        """A copy of one thread's book (zeros if it has none), or with
        no ident the columns summed over every thread."""
        if ident is not None:
            return list(self._books.get(ident, self._zero))
        total = list(self._zero)
        for book in list(self._books.values()):
            for i, v in enumerate(book):
                total[i] += v
        return total
