"""The six tables TPC-H Q9 reads, with `part` and `partsupp` shaped as
the specification's dbgen shapes them (clause 4.2.3), made from a seed,
vectorised. Pandas and numpy only; imports nothing of the program.

`gen/tpch.py` makes supplier, orders, nation and lineitem's measures;
every column this generator shares with its frames is that generator's,
value for value, at the same `orders`, `structure_seed` and `--seed`,
but for `l_suppkey`. What is added comes from draws of its own, so that
no draw of `gen/tpch.py` shifts:

  part      p_partkey 0..n_part-1, n_part = max(20, orders * 2 // 15)
            (200,000 x SF); p_name five different words of the
            specification's 92 colours (clause 4.2.2.13) joined by one
            space, so 5/92 of the names hold any one word
  partsupp  exactly four rows a part: for part p and i in 0..3
            ps_suppkey = (p + i * (S // 4) + p // S) % S, S suppliers.
            The specification has the wrap count p // S inside the
            multiple of i, which repeats a supplier for a part where
            suppliers are few (75 of 1,000 parts at 7,500 orders); this
            form never does for S >= 4 and is the same at every scale
  lineitem  l_partkey uniform over the parts, l_suppkey the formula
            above at a uniform i: every line finds exactly one partsupp
            row, as in dbgen

As in `gen/tpch.py`, `structure_seed` draws what decides a program's
work: every key and foreign key, and here every name. A string column's
dictionary and the LUT a `LIKE` bakes from it are constants of the
compiled program, so a name that moved with `--seed` would make every
seed a compile-cache miss. `--seed` draws the measures: `gen/tpch.py`'s
and ps_supplycost. Every seed then has the same row counts, the same
join outputs, the same parts that hold `green` and the same groups.
"""

import numpy as np
import pandas as pd

from harness import spec

COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
WORDS_A_NAME = 5
SUPPLIERS_A_PART = 4

# the columns Q9 reads of the frames gen/tpch.py makes
KEPT = {"supplier": ["s_suppkey", "s_nationkey"],
        "orders": ["o_orderkey", "o_orderdate"],
        "nation": ["n_nationkey", "n_name"],
        "lineitem": ["l_orderkey", "l_quantity", "l_extendedprice",
                     "l_discount"]}


def supplier_of(part, i, n_supp):
    """The i-th of a part's four suppliers, keys from 0."""
    return (part + i * (n_supp // SUPPLIERS_A_PART) + part // n_supp) % n_supp


def part_names(rs, n_part):
    """n_part names of five different colours each, in a drawn order."""
    picks = rs.permuted(np.tile(np.arange(len(COLORS), dtype=np.int8),
                                (n_part, 1)), axis=1)[:, :WORDS_A_NAME]
    words = np.asarray(COLORS)[picks]
    names = words[:, 0]
    for k in range(1, WORDS_A_NAME):
        names = np.char.add(np.char.add(names, " "), words[:, k])
    return names


def generate(params, seed, data_dir=None):
    """Return {"frames": {table: DataFrame}, "rows": {table: rows}} for the
    six tables Q9 reads. Nothing is written to disk."""
    base = spec.load_module("gen", "tpch").generate(params, seed)["frames"]
    frames = {t: base[t][cols] for t, cols in KEPT.items()}
    n_supp = len(frames["supplier"])
    n_li = len(frames["lineitem"])
    n_part = max(20, int(params["orders"]) * 2 // 15)

    # streams of their own: gen/tpch.py draws from the bare seeds
    rs = np.random.default_rng([int(params["structure_seed"]), 9])
    r = np.random.default_rng([int(seed), 9])

    part = pd.DataFrame({"p_partkey": np.arange(n_part, dtype=np.int64),
                         "p_name": part_names(rs, n_part)})
    ps_part = np.repeat(np.arange(n_part, dtype=np.int64), SUPPLIERS_A_PART)
    ps_i = np.tile(np.arange(SUPPLIERS_A_PART, dtype=np.int64), n_part)
    partsupp = pd.DataFrame({
        "ps_partkey": ps_part,
        "ps_suppkey": supplier_of(ps_part, ps_i, n_supp),
        "ps_supplycost": np.round(r.uniform(1, 1000, len(ps_part)), 2)},
        copy=False)
    if partsupp.duplicated(["ps_partkey", "ps_suppkey"]).any():
        raise ValueError(f"a part repeats a supplier at {n_supp} suppliers")

    l_part = rs.integers(0, n_part, n_li)
    l_supp = supplier_of(l_part, rs.integers(0, SUPPLIERS_A_PART, n_li),
                         n_supp)
    li = frames["lineitem"]
    frames["lineitem"] = pd.DataFrame({
        "l_orderkey": li["l_orderkey"], "l_partkey": l_part,
        "l_suppkey": l_supp, "l_quantity": li["l_quantity"],
        "l_extendedprice": li["l_extendedprice"],
        "l_discount": li["l_discount"]}, copy=False)
    frames["part"], frames["partsupp"] = part, partsupp
    return {"frames": frames,
            "rows": {t: len(df) for t, df in frames.items()}}
