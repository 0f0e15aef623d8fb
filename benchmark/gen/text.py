"""Seeded Zipf text: the corpus of the PUMA word-count deployment.

Shapes (``configs/puma-wordcount-4chip.json``; the PUMA Wikipedia dump is
not redistributable and there is no network, so the text is synthetic):

* word frequencies Zipf-Mandelbrot with exponent ``zipf_s`` 1 over
  ``vocabulary`` V word types: ``P(rank r) = ln((r + 1/2) / (r - 1/2)) /
  ln(2 V + 1)``, which is ``1 / (r ln(2 V + 1))`` to within ``1 / (12 r^2)``
  and has a closed inverse, so a file's ranks are one vectorised draw.  At
  V = 2^21 the first word is 7.2 % of all tokens;
* a word type is its rank in base 20 written in consonants, with vowels put
  between them to reach its length (so two ranks never spell one word):
  1-16 bytes, short for frequent words, mean near 5 over the tokens;
* of the types past rank 1024 one in 50 is a url of 32-200 bytes, which
  makes about 1 % of the tokens long: no fixed-width key fits;
* tokens are separated by one space, by a newline where a line passes 80
  bytes, and by a tab at one token in 997;
* ``--seed`` draws the tokens, it does not spell the words: the vocabulary
  is the configuration's, as a language's is.  Which shard a hub word is
  routed to follows from its spelling, and with a spelling a seed the two
  most frequent words fell on one shard in some seeds (34 % of all rows to
  it, a fourth round of the exchange, 24.6 s a job) and on two in others
  (29 %, three rounds, 22.6 s): PERF.md section 6, PR 30.  With ``SPELLING``
  the first word is alone on its shard, which takes 29.8 % of the rows.

Everything is numpy in bulk: the benchmark makes the corpus anew for every
seed, and that time is set-up.
"""

import os

import numpy as np

WORD_BYTES = 16             # the longest plain word
LONG_MIN, LONG_MAX = 32, 200
LONG_AFTER = 1024           # no type up to this rank is long
LONG_ONE_IN = 50
LINE_BYTES = 80
TAB_ONE_IN = 997
SPELLING = 4                # fixes where the vowels go: see the docstring
_CONSONANTS = np.frombuffer(b"bcdfghjklmnpqrstvwxz", np.uint8)
_VOWELS = np.frombuffer(b"aeiou", np.uint8)
_URL = b"http://en.wikipedia.org/wiki/"
_URL_FILL = np.frombuffer(b"0123456789abcdef_%", np.uint8)
_EXTRA_BY_DIGITS = np.array([0, 1, 2, 4, 7, 11, 11])   # vowels, at most


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: a hash of each u64."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _digits(ranks: np.ndarray):
    """How many base-20 digits ``rank - 1`` has (no leading zero but for
    zero itself), and a function ``k -> its k-th digit from the left``."""
    v = (ranks - 1).astype(np.int64)
    nd = np.ones(len(v), np.int64)
    for k in range(1, 6):
        nd += v >= 20 ** k

    def kth(k: int) -> np.ndarray:
        return (v // 20 ** np.maximum(nd - 1 - k, 0)) % 20
    return nd, kth


class Vocabulary:
    """The V word types: ``rows[r - 1]`` are rank r's bytes in
    a zero-padded row of ``WORD_BYTES`` (a long type's row is all zero and
    its bytes are ``long_rows[long_index[r - 1]]``, zero-padded to a
    multiple of ``WORD_BYTES``)."""

    def __init__(self, size: int):
        ranks = np.arange(1, size + 1, dtype=np.int64)
        h = _mix(ranks.astype(np.uint64) + np.uint64(
            SPELLING * 0x9E3779B97F4A7C15 % 2 ** 64))
        nd, digit = _digits(ranks)
        self.is_long = (ranks > LONG_AFTER) & (h % np.uint64(LONG_ONE_IN)
                                               == 0)
        # -- plain words: consonant digits with vowels between them ---------
        extra = ((h >> np.uint64(8)) % (_EXTRA_BY_DIGITS[nd] + 1).astype(
            np.uint64)).astype(np.int64)
        extra = np.minimum(extra, WORD_BYTES - nd)
        self.rows = np.zeros((size, WORD_BYTES), np.uint8)
        at = np.zeros(size, np.int64)           # where the next byte goes
        every = np.arange(size)
        for k in range(6):                      # k-th consonant from the left
            has = nd > k
            self.rows[every[has], at[has]] = _CONSONANTS[digit(k)[has]]
            at += has
            nvow = np.where(has, extra // nd + (k < extra % nd), 0)
            for j in range(int(nvow.max(initial=0))):
                put = nvow > j
                pick = (h >> np.uint64(16 + 3 * ((k * 4 + j) % 15))) \
                    % np.uint64(5)
                self.rows[every[put], at[put]] = _VOWELS[
                    pick[put].astype(np.int64)]
                at += put
        self.lens = np.where(self.is_long, 0, at)
        self.rows[self.is_long] = 0
        # -- long types: a url that ends in the rank's consonants -----------
        lr = ranks[self.is_long]
        lh = h[self.is_long]
        self.long_index = np.cumsum(self.is_long) - 1
        width = -(-LONG_MAX // WORD_BYTES) * WORD_BYTES
        self.long_lens = (LONG_MIN + (lh >> np.uint64(12))
                          % np.uint64(LONG_MAX - LONG_MIN + 1)).astype(np.int64)
        rows = np.zeros((len(lr), width), np.uint8)
        rows[:, :len(_URL)] = np.frombuffer(_URL, np.uint8)
        lnd, ldigit = _digits(lr)
        col = np.full(len(lr), len(_URL))
        for k in range(6):
            has = lnd > k
            rows[np.flatnonzero(has), col[has]] = _CONSONANTS[ldigit(k)[has]]
            col += has
        for j in range(len(_URL) + 1, LONG_MAX):    # the rest: filler
            put = (j >= col) & (j < self.long_lens)
            fill = _mix(lh + np.uint64(j)) % np.uint64(len(_URL_FILL))
            rows[np.flatnonzero(put), j] = _URL_FILL[
                fill[put].astype(np.int64)]
        self.long_rows = rows


def draw_ranks(rng, n: int, size: int) -> np.ndarray:
    """``n`` ranks in [1, size] by the law in the module's docstring."""
    x = 0.5 * np.exp(rng.random(n) * np.log(2.0 * size + 1.0))
    return np.clip(np.rint(x).astype(np.int64), 1, size)


def make_text(vocab: Vocabulary, rng, nbytes: int) -> bytes:
    """At least ``nbytes`` bytes of text, cut after the token that passes
    them, with a newline at its end."""
    size = vocab.rows.shape[0]
    mean = 7.0                      # bytes a token with its separator: a guess
    pieces, have = [], 0
    while have < nbytes:
        n = int((nbytes - have) / mean) + 4096
        ranks = draw_ranks(rng, n, size) - 1
        long_ = vocab.is_long[ranks]
        # a long token takes several rows of WORD_BYTES; the separator
        # follows its last row
        nrows = np.where(long_, -(-vocab.long_lens[vocab.long_index[ranks]]
                                  // WORD_BYTES), 1)
        first = np.cumsum(nrows) - nrows
        total = int(nrows.sum())
        block = np.zeros((total, WORD_BYTES + 1), np.uint8)
        plain = np.flatnonzero(~long_)
        block[first[plain], :WORD_BYTES] = vocab.rows[ranks[plain]]
        longs = np.flatnonzero(long_)
        tok = np.repeat(longs, nrows[longs])
        part = np.arange(len(tok)) - np.repeat(
            np.cumsum(nrows[longs]) - nrows[longs], nrows[longs])
        lrows = vocab.long_rows.reshape(len(vocab.long_rows), -1, WORD_BYTES)
        block[first[tok] + part, :WORD_BYTES] = lrows[
            vocab.long_index[ranks[tok]], part]
        length = np.where(long_, vocab.long_lens[vocab.long_index[ranks]],
                          vocab.lens[ranks])
        end = np.cumsum(length + 1) + have
        sep = np.full(n, ord(" "), np.uint8)
        line = end // LINE_BYTES
        sep[1:][line[1:] != line[:-1]] = ord("\n")
        sep[rng.integers(0, TAB_ONE_IN, n) == 0] = ord("\t")
        block[first + nrows - 1, WORD_BYTES] = sep
        flat = block.ravel()
        flat = flat[flat != 0]
        pieces.append(flat)
        have += len(flat)
    text = np.concatenate(pieces)
    # cut after the first separator at or past nbytes
    seps = (text == ord(" ")) | (text == ord("\n")) | (text == ord("\t"))
    cut = nbytes - 1 + int(np.argmax(seps[nbytes - 1:]))
    text = text[:cut + 1]
    text[cut] = ord("\n")
    return text.tobytes()


def make_corpus(outdir: str, nfiles: int, file_bytes: int, seed: int,
                vocabulary: int):
    """Write ``nfiles`` files of at least ``file_bytes`` bytes; returns
    their paths.  The same arguments give the same bytes."""
    os.makedirs(outdir, exist_ok=True)
    vocab = Vocabulary(vocabulary)
    paths = []
    for i in range(nfiles):
        rng = np.random.default_rng([seed, i])
        path = os.path.join(outdir, f"part-{i:05d}.txt")
        with open(path, "wb") as f:
            f.write(make_text(vocab, rng, file_bytes))
        paths.append(path)
    return paths
