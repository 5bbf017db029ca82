"""TPC-H substitution parameters for Q1 and Q3-Q10, drawn uniformly from
the specification's domains (clause 2.4) in this data's encoding: dates as
days since 1992-01-01, names as dictionary codes, Q9's COLOR as the word."""

from __future__ import annotations

from typing import Dict

import numpy as np

N_SEGMENTS, N_REGIONS, N_NATIONS, N_TYPES = 5, 5, 25, 150
# Q9's COLOR: one of the words that P_NAME is made of
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush brown "
    "burlywood burnished chartreuse chiffon chocolate coral cornflower cornsilk cream "
    "cyan dark deep dim dodger drab firebrick floral forest frosted gainsboro ghost "
    "goldenrod green grey honeydew hot indian ivory khaki lace lavender lawn lemon "
    "light lime linen magenta maroon medium metallic midnight mint misty moccasin "
    "navajo navy olive orange orchid pale papaya peach peru pink plum powder puff "
    "purple red rose rosy royal saddle salmon sandy seashell sienna sky slate smoke "
    "snow spring steel tan thistle tomato turquoise violet wheat white yellow"
).split()
# region of each nation, by nation code (TPC-H's NATION table)
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3, 4, 2, 3, 3, 1]


def _day(y: int, m: int, d: int = 1) -> int:
    return int((np.datetime64(f"{y:04d}-{m:02d}-{d:02d}") - np.datetime64("1992-01-01")).astype(int))


def sample(template: str, rng: np.random.Generator) -> Dict[str, float]:
    if template == "q1":
        return {"delta": int(rng.integers(60, 121))}
    if template == "q3":
        return {
            "segment": float(rng.integers(0, N_SEGMENTS)),
            "date": float(_day(1995, 3) + rng.integers(0, 31)),
        }
    if template == "q4":
        y = int(rng.integers(1993, 1998))
        m = int(rng.integers(1, 13)) if y < 1997 else int(rng.integers(1, 11))
        return {"date": float(_day(y, m))}
    if template == "q5":
        return {
            "region": float(rng.integers(0, N_REGIONS)),
            "date": float(_day(int(rng.integers(1993, 1998)), 1)),
        }
    if template == "q6":
        return {
            "date": float(_day(int(rng.integers(1993, 1998)), 1)),
            "discount": float(rng.integers(2, 10)) / 100.0,
            "quantity": float(rng.integers(24, 26)),
        }
    if template == "q7":
        n1 = int(rng.integers(0, N_NATIONS))
        n2 = int(rng.integers(0, N_NATIONS - 1))
        if n2 >= n1:
            n2 += 1
        return {"nation1": float(n1), "nation2": float(n2)}
    if template == "q8":
        nation = int(rng.integers(0, N_NATIONS))
        return {
            "nation": float(nation),
            "region": float(NATION_REGION[nation]),
            "type": float(rng.integers(0, N_TYPES)),
        }
    if template == "q9":
        return {"color": COLORS[int(rng.integers(0, len(COLORS)))]}
    if template == "q10":
        # the first of a month from February 1993 to January 1995
        months = [(y, m) for y in (1993, 1994) for m in range(1, 13)][1:] + [(1995, 1)]
        y, m = months[int(rng.integers(0, len(months)))]
        return {"date": float(_day(y, m))}
    raise KeyError(template)
