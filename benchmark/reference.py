"""The plain host reference that decides ``correct``.

It imports nothing of ``adam_tpu`` and takes nothing the program made
except the output records under test.  From the generator's input SAM,
known-sites VCF and truth file it computes, independently:

* **records** — every input record comes out exactly once, with its
  sequence and its flags (the duplicate bit aside) unchanged;
* **markdup** — Picard/ADAM duplicate semantics (bucket by read group
  and name; group by library and the 5'-unclipped position pair; keep
  the bucket with the highest sum of base qualities >= 15, ties to the
  smallest (read group, name)); compared flag by flag;
* **MD** — the MD tag of every record is the one its output alignment
  has against the simulated genome;
* **realign** — of the reads whose input alignment costs more
  (mismatches plus indel operations, against the genome) than their true
  alignment, the share whose output alignment still does: the
  misaligned reads the realigner left;
* **BQSR** — the recalibration table observed over the output records
  (primary, mapped, not duplicate, 0 < MAPQ < 255; residues with
  0 < Q, an ACGT base, a reference position, not a known SNP), solved
  with the global -> quality -> cycle/dinucleotide log-delta stack in
  float64, and applied to every residue with Q >= 5; compared residue
  by residue.

The comparison runs in blocks of rows, on a few threads, so that it
fits beside the program's process and ends well inside a run.
"""

from __future__ import annotations

import os
import re
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FLAG_PAIRED = 0x1
FLAG_UNMAPPED = 0x4
FLAG_REVERSE = 0x10
FLAG_FIRST = 0x40
FLAG_SECOND = 0x80
FLAG_SECONDARY = 0x100
FLAG_QCFAIL = 0x200
FLAG_DUP = 0x400
FLAG_SUPPLEMENTARY = 0x800

N_QUAL = 94
N_DINUC = 17
DINUC_NONE = 16
MIN_ACCEPTABLE_QUALITY = 5
MAX_QUAL = 50
BLOCK = 65536
THREADS = min(8, os.cpu_count() or 1)

_CODE = np.full(256, 4, np.uint8)
for _i, _c in enumerate(b"ACGT"):
    _CODE[_c] = _i
_COMP = np.array([3, 2, 1, 0, 4], np.uint8)
_CIGAR = re.compile(r"(\d+)([MIDNSHP=X])")


# ------------------------------------------------------------- inputs

#: The mandatory SAM fields plus the generator's RG and MD tags.
_SAM_RE = (r"^(?P<name>[^\t]*)\t(?P<flag>\d+)\t(?P<rname>[^\t]*)\t"
           r"(?P<pos>\d+)\t[^\t]*\t(?P<cigar>[^\t]*)\t[^\t]*\t[^\t]*\t"
           r"[^\t]*\t(?P<seq>[^\t]*)\t(?P<qual>[^\t]*)"
           r"(?:\tRG:Z:(?P<rg>[^\t]*))?(?:\tMD:Z:(?P<md>[^\t]*))?")
#: Every CIGAR the generator writes or the realigner makes of it:
#: [clip] M [insertion | deletion] [M] [clip].
_CIGAR_RE = (r"^(?:(?P<lead>\d+)S)?(?P<m1>\d+)M(?:(?P<il>\d+)I|(?P<dl>\d+)D)?"
             r"(?:(?P<m2>\d+)M)?(?:(?P<trail>\d+)S)?$")


def _ids(names, flags: np.ndarray) -> np.ndarray:
    """The generator names pair ``p`` ``r<p>``: read ``2p`` is its first
    of pair, ``2p + 1`` its second."""
    import pyarrow as pa
    import pyarrow.compute as pc

    pair = pc.utf8_slice_codeunits(names, 1).cast(pa.int64()).to_numpy()
    return 2 * pair + ((flags & FLAG_SECOND) != 0)


def _np(a, dtype=np.int64, fill=-1) -> np.ndarray:
    import pyarrow as pa

    return a.cast(pa.int64()).fill_null(fill).to_numpy().astype(dtype)


def _cigar_cols(cigars) -> dict:
    """Lengths of the [clip] M [I|D] [M] [clip] parts of each CIGAR;
    ``ok`` is False where a CIGAR has another shape."""
    import pyarrow.compute as pc

    m = pc.extract_regex(cigars, _CIGAR_RE)
    # an optional group that did not take part comes back as ""
    out = {k: _np(pc.replace_substring_regex(m.field(k), "^$", "0"), fill=0)
           for k in ("lead", "m1", "il", "dl", "m2", "trail")}
    out["ok"] = np.asarray(m.is_valid())
    return out


def _str_matrix(a, lmax: int, lut=None, offset: int = 0,
                pad: int = 255) -> tuple:
    """Arrow string column -> (u8[N, lmax] padded, lengths), straight
    from its buffers."""
    import pyarrow as pa

    if isinstance(a, pa.ChunkedArray):
        a = a.combine_chunks()
    if a.type != pa.large_string():
        a = a.cast(pa.large_string())
    n = len(a)
    offs = np.frombuffer(a.buffers()[1], np.int64)[a.offset: a.offset + n + 1]
    data = a.buffers()[2]
    data = (np.frombuffer(data, np.uint8) if data is not None
            else np.zeros(0, np.uint8))
    lens = np.diff(offs)
    if lut is not None:
        data = lut[data]
    else:
        data = data - np.uint8(offset)
    out = np.full((n, lmax), pad, np.uint8)
    col = np.arange(lmax)[None, :]
    inside = col < lens[:, None]
    out[inside] = data[(offs[:-1, None] + col)[inside]]
    return out, lens


def load_input(sam: str) -> dict:
    """The input SAM as columns ordered by read id."""
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.csv as pcsv

    lines = pcsv.read_csv(
        sam, read_options=pcsv.ReadOptions(column_names=["l"]),
        parse_options=pcsv.ParseOptions(delimiter="\x01", quote_char=False,
                                        escape_char=False),
        convert_options=pcsv.ConvertOptions(
            column_types={"l": pa.large_string()}),
    )["l"].combine_chunks()
    is_head = np.asarray(pc.starts_with(lines, "@"))
    rgs, libs, contigs = [], {}, []
    for h in lines.filter(pa.array(is_head)).to_pylist():
        f = h.split("\t")
        tags = dict(x.split(":", 1) for x in f[1:] if ":" in x)
        if f[0] == "@RG":
            rgs.append(tags["ID"])
            libs[tags["ID"]] = tags.get("LB", tags["ID"])
        elif f[0] == "@SQ":
            contigs.append(tags["SN"])
    m = pc.extract_regex(lines.filter(pa.array(~is_head)), _SAM_RE)
    n = len(m)
    flags = _np(m.field("flag"))
    ids = _ids(m.field("name"), flags)
    order = np.argsort(ids, kind="stable")
    if n and np.any(ids[order] != np.arange(n)):
        raise ValueError(f"{sam}: read ids are not 0..{n - 1}")
    take = pa.array(order)
    col = {k: m.field(k).take(take) for k in
           ("name", "rname", "cigar", "seq", "qual", "rg", "md")}
    lib_list = sorted(set(libs.values()))
    lib_of_rg = np.array([lib_list.index(libs[r]) for r in rgs] + [-1])
    rg = _np(pc.index_in(col["rg"], value_set=pa.array(rgs, col["rg"].type)))
    return dict(
        n=n,
        contigs=contigs,
        rg_names=rgs,
        names=col["name"],
        rname=col["rname"],
        flags=flags[order],
        contig=_np(pc.index_in(col["rname"], value_set=pa.array(
            contigs, col["rname"].type))),
        start=_np(m.field("pos"))[order] - 1,
        cigar=col["cigar"],
        seq=col["seq"],
        qual=col["qual"],
        md=col["md"],
        rg=rg,
        lib=lib_of_rg[rg],
    )


def load_known_sites(vcf: str, contigs: list) -> list:
    """Sorted 0-based known-SNP positions per contig."""
    cidx = {c: i for i, c in enumerate(contigs)}
    per = [[] for _ in contigs]
    with open(vcf) as fh:
        for line in fh:
            if line[0] == "#":
                continue
            f = line.split("\t", 2)
            per[cidx[f[0]]].append(int(f[1]) - 1)
    return [np.array(sorted(p), np.int64) for p in per]


def load_output(out_dir: str) -> dict:
    """The output Parquet parts as columns, ordered by read id."""
    import glob
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    cols = ["readName", "flags", "contig", "start", "cigar", "sequence",
            "qual", "mismatchingPositions", "recordGroupName"]
    parts = sorted(glob.glob(os.path.join(out_dir, "part-*")))
    if not parts:
        return dict(n=0, id=np.zeros(0, np.int64))
    t = pa.concat_tables([pq.read_table(p, columns=cols) for p in parts])
    flags = _np(t["flags"].combine_chunks())
    ids = _ids(t["readName"].combine_chunks(), flags)
    order = np.argsort(ids, kind="stable")
    t = t.take(pa.array(order))

    def col(k):
        return t[k].combine_chunks()

    return dict(
        n=t.num_rows,
        id=ids[order],
        flags=flags[order],
        contig=col("contig"),
        start=_np(col("start")),
        cigar=col("cigar"),
        seq=col("sequence"),
        qual=col("qual"),
        md=col("mismatchingPositions"),
        rg=col("recordGroupName"),
    )


# ------------------------------------------------------------ helpers


def _parse_cigar(c: str) -> list:
    return [(int(n), op) for n, op in _CIGAR.findall(c or "")]


def ref_positions(start: int, cigar: str, length: int) -> np.ndarray:
    """Reference position of each read base (-1: insertion or clip)."""
    out = np.full(length, -1, np.int64)
    q, r = 0, start
    for n, op in _parse_cigar(cigar):
        if op in "M=X":
            out[q: q + n] = np.arange(r, r + n)
            q += n
            r += n
        elif op in "IS":
            q += n
        elif op in "DN":
            r += n
    return out


def md_string(ref: np.ndarray, start: int, cigar: str, seq: np.ndarray) -> str:
    """The MD tag of an alignment against the genome ``ref``."""
    out, run = [], 0
    q, r = 0, start
    for n, op in _parse_cigar(cigar):
        if op in "M=X":
            for k in range(n):
                if seq[q + k] == ref[r + k]:
                    run += 1
                else:
                    out.append(f"{run}{'ACGT'[ref[r + k]]}")
                    run = 0
            q += n
            r += n
        elif op in "IS":
            q += n
        elif op == "D":
            out.append(f"{run}^{''.join('ACGT'[x] for x in ref[r: r + n])}")
            run = 0
            r += n
    out.append(str(run))
    return "".join(out)


def _positions(start, cg: dict, lens, lmax, cigars, rows) -> tuple:
    """Per-residue reference positions (-1: none) and indel-op counts of
    the reads ``rows``; ``start < 0`` marks an unmapped read."""
    col = np.arange(lmax)[None, :]
    st = start[rows][:, None]
    lead, m1, il, dl, m2 = (cg[k][rows][:, None] for k in
                            ("lead", "m1", "il", "dl", "m2"))
    q1 = col - lead
    q2 = col - lead - m1 - il
    pos = np.where((q1 >= 0) & (q1 < m1), st + q1,
                   np.where((q2 >= 0) & (q2 < m2), st + m1 + dl + q2, -1))
    mapped = start[rows] >= 0
    pos[~mapped] = -1
    n_indel = ((cg["il"][rows] > 0) | (cg["dl"][rows] > 0)).astype(np.int64)
    for j in np.flatnonzero(mapped & ~cg["ok"][rows]):
        i = rows[j]
        pos[j] = -1
        pos[j, : lens[j]] = ref_positions(int(start[i]), cigars[i].as_py(),
                                          int(lens[j]))[:lmax]
        n_indel[j] = sum(1 for _, op in _parse_cigar(cigars[i].as_py())
                         if op in "ID")
    return pos, n_indel


def _cycles(lens, flags, lmax):
    rev = (flags & FLAG_REVERSE) != 0
    second = ((flags & FLAG_PAIRED) != 0) & ((flags & FLAG_SECOND) != 0)
    initial = np.where(rev, np.where(second, -lens, lens),
                       np.where(second, -1, 1))
    inc = np.where(rev, np.where(second, 1, -1), np.where(second, -1, 1))
    return initial[:, None] + inc[:, None] * np.arange(lmax)[None, :]


def _dinucs(bases, lens, flags, lmax):
    rev = ((flags & FLAG_REVERSE) != 0)[:, None]
    b4 = np.minimum(bases, 4)
    prev_f = np.pad(b4[:, :-1], ((0, 0), (1, 0)), constant_values=4)
    next_b = np.pad(b4[:, 1:], ((0, 0), (0, 1)), constant_values=4)
    cur = np.where(rev, _COMP[b4], b4)
    prev = np.where(rev, _COMP[next_b], prev_f)
    i = np.arange(lmax)[None, :]
    in_read = i < lens[:, None]
    first_machine = np.where(rev, i == lens[:, None] - 1, i == 0)
    ok = in_read & ~first_machine & (cur < 4) & (prev < 4)
    return np.where(ok, prev.astype(np.int64) * 4 + cur, DINUC_NONE)


def solve_table(total: np.ndarray, mism: np.ndarray) -> np.ndarray:
    """Recalibrated phred for every (rg, q, cycle, dinuc) cell (float64)."""
    err = 10.0 ** (-np.arange(N_QUAL) / 10.0)
    t = total.astype(np.float64)
    m = mism.astype(np.float64)

    def emp_log(tt, mm):
        return np.log((1.0 + mm) / (2.0 + tt))

    g_t, g_m = t.sum(axis=(1, 2, 3)), m.sum(axis=(1, 2, 3))
    q_t, q_m = t.sum(axis=(2, 3)), m.sum(axis=(2, 3))
    c_t, c_m = t.sum(axis=3), m.sum(axis=3)
    d_t, d_m = t.sum(axis=2), m.sum(axis=2)
    g_exp = (err[None, :] * q_t).sum(axis=1)
    with np.errstate(divide="ignore", invalid="ignore"):
        g_delta = np.where(
            g_t > 0, emp_log(g_t, g_m) - np.log(g_exp / np.maximum(g_t, 1)),
            0.0)
        q_ok = (g_t > 0)[:, None] & (q_t > 0)
        off1 = np.log(err)[None, :] + g_delta[:, None]
        q_delta = np.where(q_ok, emp_log(q_t, q_m) - off1, 0.0)
        off2 = off1 + q_delta
        c_delta = np.where(q_ok[:, :, None] & (c_t > 0),
                           emp_log(c_t, c_m) - off2[:, :, None], 0.0)
        d_delta = np.where(q_ok[:, :, None] & (d_t > 0),
                           emp_log(d_t, d_m) - off2[:, :, None], 0.0)
    log_p = (off2[:, :, None, None] + c_delta[:, :, :, None]
             + d_delta[:, :, None, :])
    bounded = np.minimum(0.0, np.maximum(np.log(err[MAX_QUAL]), log_p))
    return np.floor(-10.0 * np.log10(np.exp(bounded)) + 0.5).astype(np.int64)


# ------------------------------------------------------------ markdup


def markdup(inp: dict) -> np.ndarray:
    """Reference duplicate mask over input read ids.

    The generator writes pairs whose two reads are both mapped or both
    unmapped, so every bucket is one pair: group by library and the two
    reads' (contig, 5' position, strand), keep the pair with the highest
    score (ties: smallest read group, then name in byte order), mark
    the rest.  Unmapped reads are never marked."""
    import pyarrow.compute as pc

    n = inp["n"]
    flags = inp["flags"]
    mapped = (flags & FLAG_UNMAPPED) == 0
    if n % 2 or np.any(mapped[0::2] != mapped[1::2]) or np.any(
            flags & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY)):
        raise ValueError("markdup reference: input is not pairs of "
                         "primary reads mapped together")
    lmax = int(max(1, pc.max(pc.utf8_length(inp["qual"])).as_py() or 1))
    quals, _ = _str_matrix(inp["qual"], lmax, offset=33, pad=0)
    score = np.where(quals >= 15, quals, 0).astype(np.int64).sum(axis=1)
    cg = _cigar_cols(inp["cigar"])
    if np.any(mapped & ~cg["ok"]):
        raise ValueError("markdup reference: a CIGAR the generator "
                         "does not write")
    span = cg["m1"] + cg["dl"] + cg["m2"]
    rev = (flags & FLAG_REVERSE) != 0
    five = np.where(rev, inp["start"] + span + cg["trail"],
                    inp["start"] - cg["lead"])
    first, second = np.arange(0, n, 2), np.arange(1, n, 2)
    pm = mapped[first]
    f, s = first[pm], second[pm]
    if np.any(inp["rg"][f] != inp["rg"][s]):
        raise ValueError("markdup reference: mates in different read groups")
    w = 1 + int(pc.max(pc.utf8_length(inp["names"])).as_py() or 1)
    nm, _ = _str_matrix(inp["names"].take(np.asarray(f)), w, pad=0)
    name_rank = np.argsort(np.argsort(
        np.ascontiguousarray(nm).view(f"S{w}").ravel(), kind="stable"))
    keys = (inp["lib"][f], inp["contig"][f], five[f], rev[f],
            inp["contig"][s], five[s], rev[s])
    order = np.lexsort((name_rank, inp["rg"][f], -(score[f] + score[s]))
                       + keys[::-1])
    grouped = np.stack([k[order] for k in keys], axis=1)
    head = np.ones(len(order), bool)
    head[1:] = np.any(grouped[1:] != grouped[:-1], axis=1)
    dup_pair = np.zeros(len(order), bool)
    dup_pair[order] = ~head
    dup = np.zeros(n, bool)
    dup[f] = dup_pair
    dup[s] = dup_pair
    return dup


# ------------------------------------------------------------ compare


def compare(inp: dict, out: dict, truth: dict, known: list,
            expect: dict) -> dict:
    """Every number that decides ``correct``, for one job's output.
    ``expect`` says which transforms the job ran: ``markdup``, ``bqsr``
    and ``known_sites`` (masking); realignment is judged against the
    truth either way."""
    import pyarrow as pa
    import pyarrow.compute as pc

    n = inp["n"]
    res = {}
    ids = out["id"]
    present = np.zeros(n, np.int64)
    ok_ids = ids[(ids >= 0) & (ids < n)]
    np.add.at(present, ok_ids, 1)
    bad = (n - int((present >= 1).sum())) + int((present > 1).sum()) \
        + int(len(ids) - len(ok_ids))
    if bad == 0:
        bad += int(np.asarray(pc.fill_null(
            pc.not_equal(out["seq"], inp["seq"]), True)).sum())
        bad += int(((out["flags"] & ~FLAG_DUP)
                    != (inp["flags"] & ~FLAG_DUP)).sum())
        in_mapped = (inp["flags"] & FLAG_UNMAPPED) == 0
        bad += int((np.asarray(pc.fill_null(pc.not_equal(
            out["contig"].cast(inp["rname"].type), inp["rname"]), True))
            & in_mapped).sum())
    res["records_wrong"] = int(bad)
    if bad:
        # the other numbers need one output record per input record
        res.update(dup_flags_wrong=n, md_wrong=n, realign_left_pct=100.0,
                   qual_wrong=n)
        return res

    want_dup = markdup(inp) if expect["markdup"] else np.zeros(n, bool)
    res["dup_flags_wrong"] = int(
        (((out["flags"] & FLAG_DUP) != 0) != want_dup).sum())

    flags = out["flags"]
    mapped = (flags & FLAG_UNMAPPED) == 0
    cidx = np.where(mapped, inp["contig"], -1)
    genome = truth["genome"]
    lmax = int(max(1, pc.max(pc.utf8_length(out["seq"])).as_py() or 1))
    starts = np.where(mapped, out["start"], -1)
    cg = _cigar_cols(out["cigar"])

    same = mapped & (out["start"] == inp["start"]) & np.asarray(
        pc.fill_null(pc.equal(out["cigar"], inp["cigar"]), False))
    md_eq = np.asarray(pc.fill_null(pc.equal(out["md"], inp["md"]), False))
    md_wrong = int((same & ~md_eq).sum())
    seq_all = out["seq"]
    for i in np.flatnonzero(mapped & ~same):
        seq = _CODE[np.frombuffer(seq_all[int(i)].as_py().encode(), np.uint8)]
        md_wrong += out["md"][int(i)].as_py() != md_string(
            genome[cidx[i]], int(out["start"][i]),
            out["cigar"][int(i)].as_py(), seq)
    res["md_wrong"] = int(md_wrong)

    # BQSR observation over the output alignments, block by block
    n_rg = len(inp["rg_names"]) + 1
    rg = _np(pc.index_in(out["rg"], value_set=pa.array(
        inp["rg_names"], out["rg"].type)), fill=n_rg - 1)
    n_cyc = 2 * lmax + 1
    size = n_rg * N_QUAL * n_cyc * N_DINUC
    # every input read carries MAPQ 60 (+10 once realigned): 0 < MAPQ < 255
    read_ok = (mapped
               & ((flags & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY)) == 0)
               & ((flags & (FLAG_DUP | FLAG_QCFAIL)) == 0))
    blocks = [np.arange(b0, min(n, b0 + BLOCK)) for b0 in range(0, n, BLOCK)]
    in_starts = np.where(mapped, inp["start"], -1)
    in_cg = _cigar_cols(inp["cigar"])

    def observe(rows):
        """One block's table counts and realignment counts, and what the
        apply check needs of it again."""
        bases, lens = _str_matrix(out["seq"].take(rows), lmax, lut=_CODE,
                                  pad=5)
        q, _ = _str_matrix(inp["qual"].take(rows), lmax, offset=33, pad=255)
        fl = flags[rows]
        qq = np.minimum(q, N_QUAL - 1).astype(np.int64)
        key = (((rg[rows][:, None] * N_QUAL + qq) * n_cyc
                + (_cycles(lens, fl, lmax) + lmax)) * N_DINUC
               + _dinucs(bases, lens, fl, lmax))
        ci = np.maximum(cidx[rows], 0)

        def cost_of(st, cgx, cigars):
            p, n_indel = _positions(st, cgx, lens, lmax, cigars, rows)
            refb = np.where(p >= 0, genome[ci[:, None], np.clip(
                p, 0, genome.shape[1] - 1)], 5)
            mm = (p >= 0) & (bases != refb)
            return p, mm, mm.sum(axis=1) + n_indel

        pos, is_mm, cost = cost_of(starts, cg, out["cigar"])
        _, _, cost_in = cost_of(in_starts, in_cg, inp["cigar"])
        tc = truth["truth_cost"][rows]
        mis = (tc >= 0) & mapped[rows] & (cost_in > tc)
        known_mask = np.zeros(pos.shape, bool)
        for c, sites in enumerate(known if expect["known_sites"] else []):
            sel = np.flatnonzero(cidx[rows] == c)
            if len(sel) and len(sites):
                p = pos[sel]
                j = np.minimum(np.searchsorted(sites, p), len(sites) - 1)
                known_mask[sel] = (sites[j] == p) & (p >= 0)
        rok = ((q > 0) & (q < 255) & (bases < 4) & (pos >= 0) & ~known_mask
               & read_ok[rows][:, None])
        return (np.bincount(key[rok], minlength=size),
                np.bincount(key[rok & is_mm], minlength=size),
                int(mis.sum()), int((mis & (cost > tc)).sum()),
                (rows, lens, q, key.astype(np.int32)))

    def apply_wrong(kept, table):
        rows, lens, q, key = kept
        got, _ = _str_matrix(out["qual"].take(rows), lmax, offset=33, pad=255)
        in_read = np.arange(lmax)[None, :] < lens[:, None]
        apply = in_read & (q >= MIN_ACCEPTABLE_QUALITY) & (q < 255)
        want = np.where(apply & expect["bqsr"], table[key], q)
        return int((in_read & (got != want)).sum())

    # numpy and pyarrow let go of the GIL in the heavy parts, so blocks
    # run side by side on threads
    with ThreadPoolExecutor(THREADS) as ex:
        obs = list(ex.map(observe, blocks))
        total = sum(o[0] for o in obs)
        mism = sum(o[1] for o in obs)
        res["realign_left_pct"] = 100.0 * sum(o[3] for o in obs) / max(
            1, sum(o[2] for o in obs))
        table = solve_table(
            total.reshape(n_rg, N_QUAL, n_cyc, N_DINUC),
            mism.reshape(n_rg, N_QUAL, n_cyc, N_DINUC)).ravel()
        wrong = sum(ex.map(lambda o: apply_wrong(o[4], table), obs))
    res["qual_wrong"] = wrong
    return res
