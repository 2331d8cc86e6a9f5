"""TPC-H ORDERS and CUSTOMER with the specification's comment text
(seeded, pruned, no per-row Python): the generator of the
``tpch-sf10-chip1-orders`` configuration.

``benchmark/datagen/tpch.py`` writes ``o_comment`` as "order comment N",
on which Q13's ``not like '%special%requests%'`` keeps every row.  Here
``o_comment`` is made as dbgen makes it (specification clause 4.2.2.10):
a pool of pseudo-text is generated once from the specification's grammar
and weighted word lists, and every comment is a substring of it at a
random offset, its length uniform in 19..78 bytes (O_COMMENT is
``varchar(79)``; dbgen draws 0.4..1.6 times the average of 49) — so a
comment starts and ends mid-word as dbgen's do, practically every row is
distinct, and about one order in a hundred holds "special" followed
later by "requests".

The key columns are drawn as ``datagen/tpch.py`` draws them:
``o_orderkey`` 1..n in order, ``o_custkey`` uniform over the lower two
thirds of the customers (dbgen never gives a customer whose key is a
multiple of three an order; either way a third have none),
``c_custkey`` 1..n.  The tables hold Q13's columns and a few cheap
neighbours, not the full schema: Parquet reads only referenced columns.

The word lists, their weights and the grammar's weights are written
down from the specification's text and dbgen's ``dists.dss`` as
remembered; the configuration's ``assumed`` says so, and
``benchmark/tests/test_datagen_tpch_orders.py`` holds the share the
pattern excludes to a band.
"""
from __future__ import annotations

import os
import shutil

import numpy as np

TABLES = ("customer", "orders")

_SCHEMA_VERSION = "v1"

#: rows a Parquet file of orders, and a row group: one staged batch
FILE_ROWS = 1 << 20

#: O_COMMENT's lengths, uniform and inclusive
COMMENT_MIN, COMMENT_MAX = 19, 78

#: dates are DAYS since 1970-01-01, as datagen/tpch.py has them
_DATE_LO, _DATE_HI = 8035, 10591
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
             "HOUSEHOLD"]


def _weighted(text: str):
    words, weights = [], []
    for item in text.split(","):
        word, _, weight = item.strip().partition("|")
        words.append(word)
        weights.append(float(weight))
    p = np.asarray(weights)
    return words, p / p.sum()


_NOUNS = _weighted(
    "packages|40, requests|40, accounts|40, deposits|40, foxes|20, "
    "ideas|20, theodolites|20, pinto beans|20, instructions|20, "
    "dependencies|10, excuses|10, platelets|10, asymptotes|10, courts|5, "
    "dolphins|5, multipliers|1, sauternes|1, warthogs|1, frets|1, dinos|1, "
    "attainments|1, somas|1, Tiresias|1, patterns|1, forges|1, braids|1, "
    "frays|1, warhorses|1, dugouts|1, notornis|1, epitaphs|1, pearls|1, "
    "tithes|1, waters|1, orbits|1, gifts|1, sheaves|1, depths|1, "
    "sentiments|1, decoys|1, realms|1, pains|1, grouches|1, escapades|1, "
    "hockey players|1")
_VERBS = _weighted(
    "sleep|20, wake|20, are|20, cajole|20, haggle|20, nag|10, use|10, "
    "boost|10, affix|5, detect|5, integrate|5, maintain|1, nod|1, was|1, "
    "lose|1, sublate|1, solve|1, thrash|1, promise|1, engage|1, hinder|1, "
    "print|1, x-ray|1, breach|1, eat|1, grow|1, impress|1, mold|1, "
    "poach|1, serve|1, run|1, dazzle|1, snooze|1, doze|1, unwind|1, "
    "kindle|1, play|1, hang|1, believe|1, doubt|1")
_ADJECTIVES = _weighted(
    "special|20, pending|20, unusual|20, express|20, furious|1, sly|1, "
    "careful|1, blithe|1, quick|1, fluffy|1, slow|1, quiet|1, ruthless|1, "
    "thin|1, close|1, dogged|1, daring|1, brave|1, stealthy|1, "
    "permanent|1, enticing|1, idle|1, busy|1, regular|20, final|40, "
    "ironic|40, even|30, bold|20, silent|10")
_ADVERBS = _weighted(
    "sometimes|1, always|1, never|1, furiously|50, slyly|50, carefully|50, "
    "blithely|40, quickly|30, fluffily|20, slowly|1, quietly|1, "
    "ruthlessly|1, thinly|1, closely|1, doggedly|1, daringly|1, bravely|1, "
    "stealthily|1, permanently|1, enticingly|1, idly|1, busily|1, "
    "regularly|1, finally|1, ironically|1, evenly|1, boldly|1, silently|1")
_PREPOSITIONS = _weighted(
    "about|50, above|50, according to|50, across|50, after|50, against|40, "
    "along|40, alongside of|30, among|30, around|20, at|10, atop|1, "
    "before|1, behind|1, beneath|1, beside|1, besides|1, between|1, "
    "beyond|1, by|1, despite|1, during|1, except|1, for|1, from|1, "
    "in place of|1, inside|1, instead of|1, into|1, near|1, of|1, on|1, "
    "outside|1, over|1, past|1, since|1, through|1, throughout|1, to|1, "
    "toward|1, under|1, until|1, up|1, upon|1, without|1, with|1, within|1")
_AUXILIARIES = _weighted(
    "do|1, may|1, might|1, shall|1, will|1, would|1, can|1, could|1, "
    "should|1, ought to|1, must|1, will have to|1, shall have to|1, "
    "could have to|1, should have to|1, must have to|1, need to|1, "
    "try to|1")
_TERMINATORS = _weighted(".|50, ;|1, :|1, ?|1, !|1, --|1")

#: the grammar, each alternative with dbgen's weight.  N noun, J
#: adjective, D adverb, V verb, X auxiliary, P preposition, T terminator;
#: in a sentence N is a noun phrase, V a verb phrase, P a prepositional
#: phrase ("<preposition> the <noun phrase>").
_NOUN_PHRASES = (("N", 10), ("J N", 20), ("J, J N", 10), ("D J N", 50))
_VERB_PHRASES = (("V", 30), ("X V", 1), ("V D", 40), ("X V D", 1))
_SENTENCES = (("N V T", 3), ("N V P T", 3), ("N V N T", 3),
              ("N P V N T", 1), ("N P V P T", 1))
_WORDS = {"N": _NOUNS, "J": _ADJECTIVES, "D": _ADVERBS, "V": _VERBS,
          "X": _AUXILIARIES}


def _draw(rng, dist, k: int):
    import pyarrow as pa
    words, p = dist
    return pa.array(words).take(pa.array(rng.choice(len(words), k, p=p)))


def _mix(rng, k: int, alternatives, make):
    """``k`` strings, each made by one of ``alternatives`` (drawn by
    weight) through ``make(form, count)``, in random order."""
    import pyarrow as pa
    w = np.asarray([a[1] for a in alternatives], float)
    counts = rng.multinomial(k, w / w.sum())
    parts = [make(form, int(c))
             for (form, _), c in zip(alternatives, counts) if c]
    return pa.concat_arrays(parts).take(pa.array(rng.permutation(k)))


def _join(parts, sep=" "):
    import pyarrow.compute as pc
    return parts[0] if len(parts) == 1 \
        else pc.binary_join_element_wise(*parts, sep)


def _phrases(rng, k: int, alternatives):
    def make(form, count):
        parts = []
        for token in form.split(" "):
            words = _draw(rng, _WORDS[token.rstrip(",")], count)
            if token.endswith(","):
                import pyarrow.compute as pc
                words = pc.binary_join_element_wise(words, "", ",")
            parts.append(words)
        return _join(parts)
    return _mix(rng, k, alternatives, make)


def text_pool(rng, nbytes: int) -> np.ndarray:
    """At least ``nbytes`` of the specification's pseudo-text as uint8:
    sentences of the grammar, a space after each."""
    import pyarrow.compute as pc

    def sentences(form, count):
        parts = []
        for token in form.split(" "):
            if token == "N":
                parts.append(_phrases(rng, count, _NOUN_PHRASES))
            elif token == "V":
                parts.append(_phrases(rng, count, _VERB_PHRASES))
            elif token == "P":
                parts.append(_join([
                    _draw(rng, _PREPOSITIONS, count),
                    _phrases(rng, count, _NOUN_PHRASES)], " the "))
            else:  # T: the terminator follows its word with no space
                last = parts.pop()
                parts.append(pc.binary_join_element_wise(
                    last, _draw(rng, _TERMINATORS, count), ""))
        return pc.binary_join_element_wise(_join(parts), "", " ")

    # a sentence is about 45 bytes; made in pieces until there is enough
    chunks, have = [], 0
    while have < nbytes:
        k = max(1000, (nbytes - have) // 40)
        arr = _mix(rng, k, _SENTENCES, sentences)
        data = np.frombuffer(arr.buffers()[2], dtype=np.uint8)
        chunks.append(data)
        have += data.size
    return np.concatenate(chunks)


def comments(rng, pool: np.ndarray, n: int):
    """``n`` comments as an Arrow string array: substrings of the pool
    at uniform offsets, lengths uniform in COMMENT_MIN..COMMENT_MAX."""
    import pyarrow as pa
    lens = rng.integers(COMMENT_MIN, COMMENT_MAX + 1, n).astype(np.int32)
    starts = rng.integers(0, pool.size - COMMENT_MAX, n).astype(np.int32)
    offsets = np.zeros(n + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    # byte j of the output comes from pool[start(row) + j - offset(row)]
    src = np.repeat(starts - offsets[:-1], lens) \
        + np.arange(offsets[-1], dtype=np.int32)
    return pa.StringArray.from_buffers(
        n, pa.py_buffer(offsets), pa.py_buffer(pool[src]))


def _pool_bytes(n_orders: int) -> int:
    """dbgen's pool is 300 MB at every scale; this one is 32 MiB from
    SF1 up (15M draws of offset and length from it repeat one comment in
    a few hundred) and smaller below, so a test makes it in a moment."""
    return int(min(32 << 20, max(1 << 18, n_orders * 21)))


def _write(path: str, names, arrays, **kw) -> None:
    import pyarrow as pa
    import pyarrow.parquet as pq
    pq.write_table(pa.Table.from_arrays(arrays, names=names), path, **kw)


def _gen_customer(rng, out: str, n: int) -> None:
    import pyarrow as pa
    _write(os.path.join(out, "part-0.parquet"),
           ["c_custkey", "c_nationkey", "c_acctbal", "c_mktsegment"],
           [pa.array(np.arange(1, n + 1, dtype=np.int32)),
            pa.array(rng.integers(0, 25, n).astype(np.int32)),
            pa.array(np.round(rng.uniform(-999.99, 9999.99, n), 2)),
            pa.array(_SEGMENTS).take(pa.array(rng.integers(0, 5, n)))])


def _gen_orders(rng, out: str, n: int, n_cust: int) -> None:
    """Files of FILE_ROWS rows (the last shorter), one row group each;
    ``o_comment`` written PLAIN: a dictionary of distinct values is a
    copy of the column, and the footer's uncompressed size then says
    what the comments decode to (benchmark/harness/like_bytes.py)."""
    import pyarrow as pa
    pool = text_pool(rng, _pool_bytes(n))
    for part, lo in enumerate(range(0, n, FILE_ROWS)):
        k = min(FILE_ROWS, n - lo)
        _write(os.path.join(out, f"part-{part}.parquet"),
               ["o_orderkey", "o_custkey", "o_totalprice", "o_orderdate",
                "o_comment"],
               [pa.array(np.arange(lo + 1, lo + k + 1, dtype=np.int32)),
                # dbgen: only ~2/3 of customers have orders
                pa.array(rng.integers(1, max(n_cust * 2 // 3, 2), k)
                         .astype(np.int32)),
                pa.array(np.round(rng.uniform(800.0, 500_000.0, k), 2)),
                pa.array(rng.integers(_DATE_LO, _DATE_HI - 121, k)
                         .astype(np.int32), type=pa.date32()),
                comments(rng, pool, k)],
               row_group_size=FILE_ROWS,
               use_dictionary=False)


def table_row_counts(sf: float) -> dict:
    """As datagen/tpch.py counts them."""
    return {"customer": max(30, int(150_000 * sf)),
            "orders": max(300, int(1_500_000 * sf))}


def generate(data_dir: str, sf: float, seed: int, tables=None) -> dict:
    """Generate (or re-use) ``customer`` and ``orders`` under
    ``data_dir``; returns {table: rows}.  One stamp a table; each table
    has a random stream of its own, so either is made alone."""
    counts = table_row_counts(sf)
    want = [t for t in TABLES if t in set(tables or TABLES)]
    unknown = set(tables or ()) - set(TABLES)
    if unknown:
        raise ValueError(f"tpch_orders generates {TABLES}, not "
                         f"{sorted(unknown)}")
    stamp = f"_{_SCHEMA_VERSION}_sf{sf:g}_seed{seed}"
    for i, t in enumerate(TABLES):
        out = os.path.join(data_dir, t)
        if t not in want or os.path.exists(os.path.join(out, stamp)):
            continue
        shutil.rmtree(out, ignore_errors=True)
        os.makedirs(out)
        rng = np.random.default_rng([seed, i])
        if t == "customer":
            _gen_customer(rng, out, counts["customer"])
        else:
            _gen_orders(rng, out, counts["orders"], counts["customer"])
        with open(os.path.join(out, stamp), "w") as f:
            f.write(stamp + "\n")
    return {t: counts[t] for t in want}
