"""Text for WordCount jobs, written as Hadoop's RandomTextWriter writes
the input of HiBench's micro/wordcount.

Each line is one record, ``key<TAB>value``: ``key_words`` words, then
``value_words`` words, each count drawn uniformly from its half-open
range, every word drawn uniformly from a fixed list of ``list_words``
words.  The list is made once from ``list_seed``: words of ``word_letters``
random lowercase letters (an inclusive range of lengths, uniform).  A job
is the records drawn from ``(seed, job)`` that fit in ``datasize`` bytes:
every job and every seed gets fresh text of about the same size, and the
block store splits it into blocks of ``block_size`` bytes at line ends,
as HDFS splits a file.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


class TextWriter:
    """The jobs of one configuration."""

    def __init__(self, cfg: Dict) -> None:
        self.cfg = cfg
        rng = np.random.default_rng(int(cfg["list_seed"]))
        lo, hi = cfg["word_letters"]
        n = int(cfg["list_words"])
        self.lens = rng.integers(lo, hi + 1, n)
        letters = rng.integers(97, 123, int(self.lens.sum()), dtype=np.uint8)
        starts = np.concatenate([[0], np.cumsum(self.lens)[:-1]])
        #: the words, each followed by one separator byte, laid end to end
        self.ext_starts = starts + np.arange(n)
        ext = np.full(len(letters) + n, ord(" "), np.uint8)
        keep = np.ones(len(ext), bool)
        keep[self.ext_starts + self.lens] = False
        ext[keep] = letters
        self.ext = ext
        self.words: List[bytes] = [
            ext[s:s + ln].tobytes() for s, ln in zip(self.ext_starts, self.lens)]

    def job(self, seed: int, job: int) -> Tuple[bytes, np.ndarray]:
        """The text of job ``job`` under ``seed``, and how often each word
        of the list occurs in it."""
        cfg = self.cfg
        size = int(cfg["datasize"])
        rng = np.random.default_rng([seed, job])
        k_lo, k_hi = cfg["key_words"]
        v_lo, v_hi = cfg["value_words"]
        mean_words = (k_lo + k_hi - 1) / 2 + (v_lo + v_hi - 1) / 2
        records = int(size / (mean_words * (self.lens.mean() + 1)) * 1.1) + 16
        keys = rng.integers(k_lo, k_hi, records)
        values = rng.integers(v_lo, v_hi, records)
        per_record = keys + values
        ids = rng.integers(0, len(self.lens), int(per_record.sum()))
        width = self.lens[ids] + 1
        ends = np.cumsum(width)
        src = np.repeat(self.ext_starts[ids] - (ends - width), width)
        src += np.arange(int(ends[-1]))
        out = self.ext[src]
        last = np.cumsum(per_record) - 1  # each record's last word
        out[ends[last - values] - 1] = ord("\t")
        out[ends[last] - 1] = ord("\n")
        fits = int(np.searchsorted(ends[last], size, side="right"))
        if fits == records:
            raise ValueError("drew too few records for datasize")
        n_words = int(per_record[:fits].sum())
        text = out[:ends[last[fits - 1]]].tobytes()
        return text, np.bincount(ids[:n_words], minlength=len(self.lens))
