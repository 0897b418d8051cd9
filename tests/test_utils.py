"""Util-layer tests: TwoBitFile (golden vs reference TwoBitSuite),
attributes (AttributeUtilsSuite), interval lists (IntervalListReaderSuite),
DNAPrefixTrie (DNAPrefixTrieSuite), flattener, instrumentation."""

import numpy as np
import pytest

from adam_tpu.ops.prefix_trie import DNAPrefixTrie
from adam_tpu.utils.attributes import TagType, parse_attribute, parse_attributes
from adam_tpu.utils.interval_list import IntervalListReader
from adam_tpu.utils.two_bit import TwoBitFile


class TestTwoBit:
    def test_hg19_chrM_golden(self, ref_resources):
        """Same expectations as TwoBitSuite.scala:27-37."""
        tb = TwoBitFile(str(ref_resources / "hg19.chrM.2bit"))
        assert tb.num_seq == 1
        assert tb.extract("hg19_chrM", 0, 10) == "GATCACAGGT"
        assert tb.extract("hg19_chrM", 503, 513) == "CATCCTACCC"
        assert tb.extract("hg19_chrM", 16561, 16571) == "CATCACGATG"

    def test_out_of_bounds(self, ref_resources):
        tb = TwoBitFile(str(ref_resources / "hg19.chrM.2bit"))
        size = tb.records["hg19_chrM"].dna_size
        with pytest.raises(ValueError):
            tb.extract("hg19_chrM", 0, size + 1)
        assert len(tb.extract("hg19_chrM", 0, size)) == size

    def test_bad_magic(self):
        with pytest.raises(ValueError):
            TwoBitFile(b"\x00" * 32)


class TestAttributes:
    def test_parse_tags(self):
        tags = parse_attributes("XT:i:3\tXU:Z:foo,bar")
        assert len(tags) == 2
        assert tags[0].tag == "XT"
        assert tags[0].tag_type is TagType.INTEGER
        assert tags[0].value == 3
        assert tags[1].tag == "XU"
        assert tags[1].tag_type is TagType.STRING
        assert tags[1].value == "foo,bar"

    def test_empty_string(self):
        assert parse_attributes("") == []

    def test_malformed_raises(self):
        with pytest.raises(ValueError):
            parse_attribute("XT:i")

    def test_string_with_colon(self):
        s = "a:b:c:d"
        tags = parse_attributes("XX:Z:" + s)
        assert len(tags) == 1 and tags[0].value == s

    def test_numeric_sequence_roundtrip(self):
        a = parse_attribute("XB:B:i,1,2,3")
        assert a.tag_type is TagType.NUMERIC_SEQUENCE
        assert a.value == ("i", [1, 2, 3])
        assert str(a) == "XB:B:i,1,2,3"

    def test_str_roundtrip(self):
        for s in ("XT:i:3", "XU:Z:foo,bar", "XA:A:c", "XF:f:1.5"):
            assert str(parse_attribute(s)) == s


class TestIntervalList:
    def test_gatk_example(self, ref_resources):
        """IntervalListReaderSuite expectations, shifted to 0-based
        half-open coordinates."""
        reader = IntervalListReader(
            str(ref_resources / "example_intervals.list")
        )
        intervals = list(reader)
        assert len(intervals) == 6
        for idx in range(1, 7):
            assert intervals[idx - 1][1] == f"target_{idx}"
        # first row is 1:30366-30503 (1-based incl) -> [30365, 30503)
        region = intervals[0][0]
        assert (region.referenceName, region.start, region.end) == (
            "1", 30365, 30503,
        )
        sd = reader.sequence_dictionary
        assert len(sd.records) == 2
        assert sd["1"].length == 249250621
        assert sd["2"].length == 243199373


class TestDNAPrefixTrie:
    def test_empty_rejected(self):
        with pytest.raises(AssertionError):
            DNAPrefixTrie({})

    def test_full_wildcard(self):
        trie = DNAPrefixTrie({"AA": 1, "TT": 2, "CC": 3})
        assert trie.size == 3
        assert len(trie.find("**")) == 3

    def test_illegal_characters(self):
        with pytest.raises(ValueError):
            DNAPrefixTrie({"ATMGC": 0})

    def test_ambiguous_keys_dropped(self):
        trie = DNAPrefixTrie({"ANCT": 0.5, "ACTN": 1.0})
        assert trie.size == 0
        assert not trie.contains("ANCT")
        assert not trie.contains("ACTN")

    def test_mixed_lengths_rejected(self):
        with pytest.raises(AssertionError):
            DNAPrefixTrie({"ACTCGA": 1.2, "ACTCA": 1.1})

    def test_insert_and_get(self):
        trie = DNAPrefixTrie({"ACCTA": 1, "ACTGA": 2, "CCTCA": 3})
        assert trie.size == 3
        for k, v in [("ACCTA", 1), ("ACTGA", 2), ("CCTCA", 3)]:
            assert trie.contains(k)
            assert trie.get(k) == v
        assert trie.get_or_else("TTTTT", 9) == 9
        assert trie.get_if_exists("TTTTT") is None
        with pytest.raises(KeyError):
            trie.get("TTTTT")

    sample = {
        "AACACT": 1, "AACACC": 4, "ATGGTC": 2, "CACTGC": 5,
        "CCTCGA": 4, "GGCGTC": 6, "TCCTCG": 4, "TTCTTC": 2,
    }

    def test_wildcard_search(self):
        found = DNAPrefixTrie(self.sample).search("A****C")
        assert found == {"AACACC": 4, "ATGGTC": 2}

    def test_prefix_search(self):
        found = DNAPrefixTrie(self.sample).prefix_search("AACA")
        assert found == {"AACACT": 1, "AACACC": 4}

    def test_suffix_search(self):
        found = DNAPrefixTrie(self.sample).suffix_search("TC")
        assert found == {"ATGGTC": 2, "GGCGTC": 6, "TTCTTC": 2}


class TestInstrumentation:
    def test_timer_report(self):
        from adam_tpu.utils.instrumentation import TimerRegistry

        reg = TimerRegistry()
        reg.recording = True
        with reg.time("Stage A"):
            pass
        with reg.time("Stage A"):
            pass
        rep = reg.report()
        assert "Stage A" in rep and "2" in rep

    def test_disabled_registry_records_nothing(self):
        from adam_tpu.utils.instrumentation import TimerRegistry

        reg = TimerRegistry()
        with reg.time("Stage A"):
            pass
        assert reg.timers == {}


class TestReviewRegressions:
    def test_a_type_must_be_single_char(self):
        with pytest.raises(ValueError):
            parse_attribute("XT:A:")
        with pytest.raises(ValueError):
            parse_attribute("XT:A:AB")

    def test_trie_depth_cap(self):
        with pytest.raises(ValueError):
            DNAPrefixTrie({"T" * 32: 1})
        t = DNAPrefixTrie({"T" * 31: 1})
        assert t.contains("T" * 31)

    def test_genotype_sort_on_save(self, ref_resources, tmp_path):
        from adam_tpu.api.datasets import GenotypeDataset

        gt = GenotypeDataset.load(str(ref_resources / "small.vcf"))
        srt = gt.sorted_by_position()
        key = list(
            zip(srt.variants.contig_idx.tolist(), srt.variants.start.tolist())
        )
        assert key == sorted(key)
        # genotype links survive the permutation
        for g_i in range(len(srt.genotypes)):
            vi = int(srt.genotypes.variant_idx[g_i])
            assert 0 <= vi < len(srt.variants)
        out = tmp_path / "gt.adam"
        gt.save(str(out), sort_on_save=True)
        rt = GenotypeDataset.load(str(out))
        key2 = list(
            zip(rt.variants.contig_idx.tolist(), rt.variants.start.tolist())
        )
        assert key2 == sorted(key2)


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    """``import adam_tpu`` leaves a set JAX_COMPILATION_CACHE_DIR alone
    and otherwise puts the persistent cache at <checkout>/.jax_cache."""
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    env = {k: v for k, v in os.environ.items()
           if k not in ("ADAM_TPU_NO_COMPILE_CACHE",
                        "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    want = str(repo / ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jcc")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, adam_tpu; print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd=str(repo), capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == want
