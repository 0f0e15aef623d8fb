"""Plain reference for word-count, and the check that holds the OINK
``wordfreq`` job to it.

``count_words`` reads the files themselves, not what the generator drew:
bounded blocks cut at whitespace, ``bytes.split()`` and a
``collections.Counter``.  Nothing of ``gpu_mapreduce_tpu`` is used to count.
``check_counts`` (named by the traffic file as ``refs.wordcount:check_counts``)
scans the job's result ``mrw`` once, shard by shard, and holds it to the
configuration's guarantees: every word's count, the sum, one shard a word,
every word decoded from its own shard's table, the printed top N.
"""

import collections
import re

import numpy as np

from benchmark import check

BLOCK = 16 << 20            # bytes read at once; a block ends at whitespace
WHITESPACE = b" \t\n\r\x0b\x0c"    # what bytes.split() splits at
RESULT = "mrw"              # the named MR object the job leaves


def count_words(paths, block: int = BLOCK) -> dict:
    """word -> occurrences over all of ``paths``; words are what
    ``bytes.split()`` gives, and none spans two files."""
    counts = collections.Counter()
    for path in paths:
        with open(path, "rb") as f:
            tail = b""
            while chunk := f.read(block):
                chunk = tail + chunk
                # keep the bytes after the last whitespace for the next
                # block: a word cut by the read is counted once, whole
                cut = 1 + max(chunk.rfind(bytes([c])) for c in WHITESPACE)
                counts.update(chunk[:cut].split())
                tail = chunk[cut:]
            counts.update(tail.split())
    return dict(counts)


def top_counts(counts: dict, n: int) -> list:
    """The n largest counts, descending."""
    return sorted(counts.values(), reverse=True)[:n]


def shard_rows(frame, p: int):
    """(ids, counts) of shard p's valid rows of a mesh-resident KV."""
    cap, n = frame.cap, int(frame.counts[p])
    out = []
    for a in (frame.key, frame.value):
        block = next(sh.data for sh in a.addressable_shards
                     if (sh.index[0].start or 0) == p * cap)
        out.append(np.asarray(block)[:n])
    return out


def check_counts(env) -> dict:
    from gpu_mapreduce_tpu.oink.objects import _mesh_frame
    want = env.memo["want"]
    frame = _mesh_frame(env.script.obj.get_mr(RESULT))
    check(frame is not None and frame.key_decode is not None,
          f"{RESULT} is not a mesh-resident dataset with word tables")
    got, per_shard = {}, []
    for p in range(frame.nprocs):
        ids, values = shard_rows(frame, p)
        table = frame.key_decode.shard(p)      # this shard's own table
        for h, v in zip(ids.tolist(), values.tolist()):
            check(h in table, f"shard {p} holds id {h:#x}, which its own "
                  f"table cannot decode")
            word = table[h]
            check(word not in got, f"{word!r} is in two shards")
            got[word] = v
        per_shard.append(len(ids))
    check(len(got) == len(want) and got.keys() == want.keys(),
          f"{len(got)} words against the reference's {len(want)}; e.g. "
          f"{sorted(set(got) ^ set(want))[:3]}")
    wrong = [(w, got[w], c) for w, c in want.items() if got[w] != c][:3]
    check(not wrong, f"counts differ from the reference (word, got, want): "
          f"{wrong}")
    ntokens = sum(want.values())
    check(sum(got.values()) == ntokens, "counts do not sum to the tokens")
    # the messages: "WordFreq: F files, W words, U unique", then "  c word"
    head = re.match(r"WordFreq: (\d+) files, (\d+) words, (\d+) unique",
                    env.messages[0])
    check(head is not None, f"unexpected message {env.messages[0]!r}")
    check((int(head[2]), int(head[3])) == (ntokens, len(want)),
          f"{env.messages[0]!r} against {ntokens} words, {len(want)} unique")
    ntop = int(env.config["shapes"]["ntop"])
    printed = [ln.split() for ln in env.messages[1:]]
    best = top_counts(want, ntop)
    # equal counts, and each word really has the count beside it: where
    # counts are distinct that fixes the words and their order
    check([int(c) for c, _w in printed] == best,
          f"printed top counts {printed} against {best}")
    check(len({w for _c, w in printed}) == len(printed),
          f"a word is printed twice: {printed}")
    for c, w in printed:
        check(want.get(w.encode()) == int(c), f"printed {w!r} with {c}")
    return {"words": ntokens, "unique": len(want),
            "unique_per_shard": per_shard, "top": printed[:3]}
