"""Seeded PUMA-density HTML corpus and its plain regex reference.

Copied from ``chip_smoke.py`` (PR 22) so that the yardstick does not move
when the program does.  Shapes, all from the source deployment
(``chapter_final.pdf`` §3.4, ``cuda_scale/InvertedIndex.cu``): ~1 href per
KB of filler; a quarter of the references hit a 64-URL hot set; 2 % are
130-210 byte long-tail URLs; one in 500 is longer than ``MAX_URL`` and must
be dropped by the system, as the reference drops it.
"""

import os
import re

MAX_URL = 256               # apps/invertedindex.MAX_URL (the job asserts it)
PATTERN = b'<a href="'


def make_corpus(outdir: str, nfiles: int, file_bytes: int, seed: int):
    """Write ``nfiles`` files of at least ``file_bytes`` bytes; returns
    their paths.  The same arguments give the same bytes."""
    filler = b"<p>" + b"lorem ipsum dolor sit amet " * 36 + b"</p>\n"
    hot = [b"http://example.org/hot/%02d" % i for i in range(64)]
    base = b"http://example.org/s%d/" % seed
    os.makedirs(outdir, exist_ok=True)
    paths, uid, nref = [], 0, 0
    for i in range(nfiles):
        pieces, size = [], 0
        while size < file_bytes:
            if nref % 500 == 499:
                u = base + b"over/p%08d/" % uid + b"y" * 300
                uid += 1
            elif nref % 50 == 49:
                u = base + b"long/p%08d/" % uid + b"x" * (96 + uid % 80)
                uid += 1
            elif nref % 4 == 3:
                u = hot[(nref // 4) % len(hot)]
            else:
                u = base + b"wiki/page-%08d" % uid
                uid += 1
            ref = PATTERN + u + b'">x</a>'
            nref += 1
            pieces.append(filler)
            pieces.append(ref)
            size += len(filler) + len(ref)
        path = os.path.join(outdir, f"part-{i:05d}.html")
        with open(path, "wb") as f:
            f.write(b"".join(pieces))
        paths.append(path)
    return paths


def index_reference(paths):
    """Plain regex scan: url -> sorted list of the indices (into ``paths``)
    of the files naming it, and the number of (url, file) hits.  An href
    whose closing quote is not within ``MAX_URL`` bytes is dropped; files
    are scanned one by one, so nothing matches across a file boundary."""
    rx = re.compile(re.escape(PATTERN) + rb'([^"]*)"')
    index, npairs = {}, 0
    for i, path in enumerate(paths):
        with open(path, "rb") as f:
            data = f.read()
        for m in rx.finditer(data):
            url = m.group(1)
            if len(url) >= MAX_URL:
                continue
            npairs += 1
            index.setdefault(url, set()).add(i)
    return {u.decode(): sorted(fs) for u, fs in index.items()}, npairs
