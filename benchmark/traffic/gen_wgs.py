"""WGS-shaped paired-end SAM from a seed: the benchmark's own copy of
``tools/make_wgs_sam.make_wgs``, with the same semantics.

Reads are sampled from a simulated genome: planted heterozygous indels
every ~2 kb (half the indel-spanning reads emitted all-M, the way an
indel-unaware aligner maps them), planted SNPs written to a known-sites
VCF, quality-correlated sequencing errors with exact MD tags, read-length
variation, soft clips, unmapped pairs, PCR duplicates, two libraries.

What differs from the original is only how the random draws are laid
out, so that the work splits over processes:

* each contig draws from its own child of ``SeedSequence(seed)``, and
  the number of pairs per contig is one multinomial draw;
* every per-read random value (soft-clip junk, unmapped sequences) is
  drawn up front, so a worker builds any subset of a contig's reads and
  the bytes do not depend on how many workers there are;
* workers split a contig by read start, so their sorted chunks
  concatenate into a coordinate-sorted file with no merge.

Besides ``input.sam`` and ``known.vcf`` it writes ``truth.npz``: the
genome and, per read, the mismatches plus indel operations of its true
alignment, which the benchmark's reference compares the realigner's
output against.  Workers are spawned, never forked, and import no JAX.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

_BASES = np.frombuffer(b"ACGT", np.uint8)
MAX_CLIP = 11  # soft clips are 3..11 bases

#: Keys of a configuration file that reach the generator.
GEN_KEYS = (
    "read_len", "n_contigs", "contig_len", "indel_every", "snp_every",
    "error_rate", "dup_frac", "clip_frac", "unmapped_frac",
    "trimmed_frac", "trimmed_min", "trimmed_max",
)


def _phred_profile(rng, n, read_len):
    """Position-dependent declining quality with per-read jitter."""
    pos = np.arange(read_len)
    base = 38.0 - 12.0 * (pos / max(read_len - 1, 1)) ** 2
    jitter = rng.normal(0, 3, (n, read_len))
    return np.clip(base[None, :] + jitter, 2, 40).astype(np.uint8)


def _md_for(ref_slice: np.ndarray, read: np.ndarray) -> str:
    """MD tag for an M-only alignment span."""
    mism = np.flatnonzero(ref_slice != read)
    out = []
    last = 0
    for m in mism:
        out.append(str(m - last))
        out.append("ACGT"[ref_slice[m]])
        last = m + 1
    out.append(str(len(read) - last))
    return "".join(out)


def _md_mismatches(md: str) -> int:
    """Mismatched bases in an MD tag (deleted bases after ``^`` excluded)."""
    n = 0
    in_del = False
    for ch in md:
        if ch == "^":
            in_del = True
        elif ch.isdigit():
            in_del = False
        elif not in_del:
            n += 1
    return n


def seed_sequence(seed: int) -> np.random.SeedSequence:
    # any whole number: SeedSequence takes non-negative entropy only
    return np.random.SeedSequence(int(seed) % (1 << 128))


class ContigState:
    """Everything of one contig that is drawn before any read is built."""

    def __init__(self, ss, contig: int, n_pairs: int, p):
        rng = np.random.default_rng(ss)
        read_len = p["read_len"]
        contig_len = p["contig_len"]
        self.ref = rng.integers(0, 4, contig_len).astype(np.uint8)
        ie, se = p["indel_every"], p["snp_every"]
        indels = {}
        pos = int(rng.integers(500, ie))
        k = 0
        while pos < contig_len - 2 * read_len:
            ln = int(rng.integers(1, 9))
            if k % 2 == 0:
                indels[pos] = (True, rng.integers(0, 4, ln).astype(np.uint8))
            else:
                indels[pos] = (False, ln)
            pos += int(rng.integers(ie // 2, ie * 3 // 2))
            k += 1
        snps = {}
        pos = int(rng.integers(100, se))
        while pos < contig_len - read_len:
            snps[pos] = (int(self.ref[pos]) + int(rng.integers(1, 4))) % 4
            pos += int(rng.integers(se // 2, se * 3 // 2))
        self.indels = indels
        self.indel_pos = np.array(sorted(indels), np.int64)
        self.snps = snps
        self.snp_pos = np.array(sorted(snps), np.int64)

        n_reads = 2 * n_pairs
        n_sites = max(1, int(n_pairs * (1.0 - p["dup_frac"])))
        # fragments end >= 100 bases inside the contig (the original's
        # 3 * read_len let a reverse read run past the contig end, with a
        # CIGAR longer than its truncated sequence)
        site_start = rng.integers(0, contig_len - 5 * read_len, n_sites)
        site_of_pair = np.concatenate(
            [np.arange(n_sites), rng.integers(0, n_sites, n_pairs - n_sites)]
        )
        rng.shuffle(site_of_pair)
        self.site_of_pair = site_of_pair
        self.pr_start = site_start[site_of_pair]
        # isize belongs to the fragment site: PCR duplicates share both
        # 5' keys, which is what makes them duplicates
        site_isize = rng.integers(int(read_len * 2.2), int(read_len * 4),
                                  n_sites)
        self.isize = site_isize[site_of_pair]
        self.hap = rng.random(n_pairs) < 0.5
        self.misalign = rng.random(n_reads) < 0.5
        lens = np.where(
            rng.random(n_reads) < 0.88, read_len,
            rng.integers(int(read_len * 0.6), read_len, n_reads),
        ).astype(np.int32)
        if p["trimmed_frac"] > 0.0:
            lens = np.where(
                rng.random(n_reads) < p["trimmed_frac"],
                rng.integers(p["trimmed_min"], p["trimmed_max"] + 1, n_reads),
                lens,
            ).astype(np.int32)
        self.lens = lens
        self.clip = np.where(
            rng.random(n_reads) < p["clip_frac"],
            rng.integers(3, MAX_CLIP + 1, n_reads), 0,
        ).astype(np.int32)
        self.unmapped_pair = rng.random(n_pairs) < p["unmapped_frac"]
        self.quals = _phred_profile(rng, n_reads, read_len)
        err_p = p["error_rate"] * np.power(
            10.0, (25.0 - self.quals.astype(np.float32)) / 30.0)
        self.err_mask = rng.random((n_reads, read_len)) < err_p
        self.junk = rng.integers(0, 4, (n_reads, MAX_CLIP)).astype(np.uint8)
        um = np.flatnonzero(np.repeat(self.unmapped_pair, 2))
        self.unmapped_seq = dict(zip(
            um.tolist(),
            rng.integers(0, 4, (len(um), read_len)).astype(np.uint8)))

        # aligned starts, aligner-style: a forward read's POS advances
        # past its leading clip; a reverse read anchors its 3'-most
        # aligned base at the fragment end
        s1 = self.pr_start.astype(np.int64)
        fe = s1 + self.isize
        self.aln_start = np.empty(n_reads, np.int64)
        self.aln_start[0::2] = s1 + self.clip[0::2]
        self.aln_start[1::2] = fe - self.lens[1::2]


def _build_read(st: ContigState, name: str, ri: int, contig_name: str):
    """One mapped read -> (SAM line, truth cost)."""
    pi = ri // 2
    first = ri % 2 == 0
    neg = not first
    start = int(st.aln_start[ri])
    mate_start = int(st.aln_start[ri ^ 1])
    tlen = int(st.isize[pi]) * (1 if first else -1)
    hap_i = bool(st.hap[pi])
    L = int(st.lens[ri])
    cl = int(st.clip[ri])
    aln_len = L - cl
    ref = st.ref
    ipos = st.indel_pos
    lo = np.searchsorted(ipos, start + 1)
    use_indel = None
    if hap_i and lo < len(ipos) and ipos[lo] < start + aln_len - 1:
        use_indel = int(ipos[lo])
    if use_indel is None:
        seq = ref[start: start + aln_len].copy()
        cig_mid = f"{aln_len}M"
        md_parts = None
    else:
        a = use_indel - start
        is_ins, payload = st.indels[use_indel]
        if is_ins:
            b = min(len(payload), aln_len - a - 1)
            rest = aln_len - a - b
            seq = np.concatenate(
                [ref[start: start + a], payload[:b],
                 ref[start + a: start + a + rest]])
            cig_mid = f"{a}M{b}I{rest}M"
            md_parts = None
        else:
            dl = int(payload)
            rest = aln_len - a
            seq = np.concatenate(
                [ref[start: start + a],
                 ref[start + a + dl: start + a + dl + rest]])
            cig_mid = f"{a}M{dl}D{rest}M"
            md_parts = (a, ref[start + a: start + a + dl], rest)
    if hap_i:
        sp = st.snp_pos
        for rp in sp[np.searchsorted(sp, start):
                     np.searchsorted(sp, start + len(seq))]:
            off = int(rp - start)
            if 0 <= off < len(seq):
                seq[off] = st.snps[int(rp)]
    for e in np.flatnonzero(st.err_mask[ri][: len(seq)]):
        seq[e] = (int(seq[e]) + int(1 + (ri + e) % 3)) % 4

    def true_md() -> str:
        if use_indel is None:
            return _md_for(ref[start: start + len(seq)], seq)
        if md_parts is None:  # insertion: MD covers the two M runs
            a = int(cig_mid.split("M")[0])
            b = int(cig_mid.split("M")[1].split("I")[0])
            rd = np.concatenate([seq[:a], seq[a + b:]])
            return _md_for(ref[start: start + len(rd)], rd)
        a, dseq, rest = md_parts
        md_a = _md_for(ref[start: start + a], seq[:a])
        md_r = _md_for(
            ref[start + a + len(dseq): start + a + len(dseq) + rest], seq[a:])
        return f"{md_a}^{''.join('ACGT'[x] for x in dseq)}{md_r}"

    md = true_md()
    truth_cost = _md_mismatches(md) + (use_indel is not None)
    if use_indel is not None and st.misalign[ri]:
        # indel-unaware alignment: all-M, the indel smeared into the MD
        cig_mid = f"{len(seq)}M"
        md = _md_for(ref[start: start + len(seq)], seq)
    if cl:
        junk = st.junk[ri, :cl]
        if not neg:
            seq = np.concatenate([junk, seq])
            cigar = f"{cl}S{cig_mid}"
        else:
            seq = np.concatenate([seq, junk])
            cigar = f"{cig_mid}{cl}S"
    else:
        cigar = cig_mid
    flags = 0x1 | 0x2 | (0x10 if neg else 0x20) | (0x40 if first else 0x80)
    q = st.quals[ri][: len(seq)]
    # read group follows the fragment: PCR copies share a library
    rg = "rg1" if st.site_of_pair[pi] % 3 else "rg2"
    nm = len(np.flatnonzero(st.err_mask[ri][: len(seq) - cl]))
    line = (
        f"{name}\t{flags}\t{contig_name}\t{start + 1}\t60\t{cigar}\t=\t"
        f"{mate_start + 1}\t{tlen}\t{_BASES[seq].tobytes().decode()}\t"
        f"{(q + 33).tobytes().decode()}\tRG:Z:{rg}\tMD:Z:{md}\tNM:i:{nm}\n"
    )
    return line, truth_cost


def _unmapped_lines(st: ContigState, name: str, pi: int) -> str:
    out = []
    for ri, flag in ((2 * pi, 77), (2 * pi + 1, 141)):
        L = int(st.lens[ri])
        seq = _BASES[st.unmapped_seq[ri][:L]].tobytes().decode()
        q = (st.quals[ri][:L] + 33).tobytes().decode()
        out.append(f"{name}\t{flag}\t*\t0\t0\t*\t*\t0\t0\t{seq}\t{q}"
                   "\tRG:Z:rg1\n")
    return "".join(out)


def _worker(task) -> tuple:
    """Build the reads of one contig whose start lies in ``[lo, hi)``
    (and, for the chunk with ``lo == 0``, the contig's unmapped pairs)."""
    (ss, contig, n_pairs, pair_offset, p, lo, hi, out_path) = task
    st = ContigState(ss, contig, n_pairs, p)
    mapped = ~np.repeat(st.unmapped_pair, 2)
    starts = st.aln_start
    rows = np.flatnonzero(mapped & (starts >= lo) & (starts < hi))
    rows = rows[np.argsort(starts[rows], kind="stable")]
    cname = f"chr{contig + 17}"
    cost = np.zeros(len(rows), np.int16)
    with open(out_path, "w") as fh:
        buf = []
        for j, ri in enumerate(rows):
            line, cost[j] = _build_read(
                st, f"r{pair_offset + ri // 2}", int(ri), cname)
            buf.append(line)
            if len(buf) >= 20000:
                fh.write("".join(buf))
                buf = []
        fh.write("".join(buf))
    unmapped, snps = "", {}
    if lo == 0:
        unmapped = "".join(
            _unmapped_lines(st, f"r{pair_offset + pi}", int(pi))
            for pi in np.flatnonzero(st.unmapped_pair))
        snps = st.snps
    return rows + 2 * pair_offset, cost, unmapped, snps


def plan(seed: int, n_reads: int, n_contigs: int):
    """(per-contig SeedSequence, pairs per contig, pair offsets)."""
    if n_reads % 2:
        raise ValueError(f"n_reads must be even (pairs), got {n_reads}")
    kids = seed_sequence(seed).spawn(n_contigs + 1)
    n_pairs = n_reads // 2
    counts = np.random.default_rng(kids[0]).multinomial(
        n_pairs, [1.0 / n_contigs] * n_contigs)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return kids[1:], counts.astype(np.int64), offsets.astype(np.int64)


def genome(seed: int, cfg: dict) -> list:
    """The reference sequence of every contig (codes 0..3), as drawn by
    the generator for ``seed``."""
    kids, _, _ = plan(seed, cfg["n_reads"], cfg["n_contigs"])
    return [np.random.default_rng(k).integers(0, 4, cfg["contig_len"])
            .astype(np.uint8) for k in kids]


def generate(out_dir: str, seed: int, cfg: dict, procs: int = 1) -> dict:
    """Write ``input.sam``, ``known.vcf`` and ``truth.npz`` for ``seed``
    under ``out_dir``; ``cfg`` holds :data:`GEN_KEYS` and ``n_reads``.
    The bytes depend on ``seed`` and ``cfg`` alone, not on ``procs``."""
    p = {k: cfg[k] for k in GEN_KEYS}
    n_contigs, contig_len = p["n_contigs"], p["contig_len"]
    kids, counts, offsets = plan(seed, cfg["n_reads"], n_contigs)
    per = max(1, -(-procs // n_contigs))  # start-range chunks per contig
    edges = np.linspace(0, contig_len, per + 1).astype(np.int64)
    edges[-1] = contig_len + 1  # a read may start at contig_len
    tasks = []
    for c in range(n_contigs):
        for k in range(per):
            tasks.append((kids[c], c, int(counts[c]), int(offsets[c]), p,
                          int(edges[k]), int(edges[k + 1]),
                          os.path.join(out_dir, f"chunk.{c}.{k}.sam")))
    if procs > 1:
        # a worker that dies breaks the pool, and the run fails, where a
        # multiprocessing.Pool would wait for it for ever
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(min(procs, len(tasks)), mp_context=ctx) as ex:
            results = list(ex.map(_worker, tasks))
    else:
        results = [_worker(t) for t in tasks]

    sam = os.path.join(out_dir, "input.sam")
    with open(sam, "wb") as out:
        head = ["@HD\tVN:1.5\tSO:coordinate\n"]
        head += [f"@SQ\tSN:chr{c + 17}\tLN:{contig_len}\n"
                 for c in range(n_contigs)]
        head += ["@RG\tID:rg1\tSM:sample\tLB:lib1\tPL:ILLUMINA\n",
                 "@RG\tID:rg2\tSM:sample\tLB:lib2\tPL:ILLUMINA\n"]
        out.write("".join(head).encode())
        for t in tasks:
            with open(t[-1], "rb") as fh:
                while True:
                    block = fh.read(1 << 24)
                    if not block:
                        break
                    out.write(block)
            os.remove(t[-1])
        for res in results:
            out.write(res[2].encode())

    truth_cost = np.full(cfg["n_reads"], -1, np.int16)  # -1: unmapped
    snps = [{} for _ in range(n_contigs)]
    for t, (rows, cost, _, s) in zip(tasks, results):
        truth_cost[rows] = cost
        snps[t[1]].update(s)
    refs = genome(seed, cfg)
    vcf = os.path.join(out_dir, "known.vcf")
    with open(vcf, "w") as fh:
        fh.write("##fileformat=VCFv4.2\n")
        for c in range(n_contigs):
            fh.write(f"##contig=<ID=chr{c + 17},length={contig_len}>\n")
        fh.write("#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
        for c in range(n_contigs):
            for pos in sorted(snps[c]):
                fh.write(f"chr{c + 17}\t{pos + 1}\t.\t"
                         f"{'ACGT'[refs[c][pos]]}\t{'ACGT'[snps[c][pos]]}"
                         "\t50\tPASS\t.\n")
    truth = os.path.join(out_dir, "truth.npz")
    np.savez(truth, truth_cost=truth_cost, genome=np.stack(refs))
    return {"sam": sam, "vcf": vcf, "truth": truth}

