"""tokenize_tool: corpus → SKYTOK shards → trainable via TokenDataset."""
import os
import subprocess
import sys

import numpy as np

from skypilot_tpu.train import tokenize_tool
from skypilot_tpu.train.data import TokenDataset, read_token_shard


def _corpus(tmp_path, n_files=3, chars=5000):
    paths = []
    for i in range(n_files):
        p = tmp_path / f'doc{i}.txt'
        p.write_text(f'document {i} ' + 'abcdefg ' * (chars // 8))
        paths.append(str(p))
    return paths


class TestTokenizeTool:

    def test_byte_corpus_round_trips(self, tmp_path):
        paths = _corpus(tmp_path)
        out = tmp_path / 'shards'
        rc = tokenize_tool.main(['--input'] + paths +
                                ['--out', str(out),
                                 '--shard-tokens', '4096'])
        assert rc == 0
        shards = sorted(p for p in os.listdir(out) if p.endswith('.bin'))
        assert len(shards) >= 3  # ~15k tokens / 4096 per shard
        tokens = np.concatenate(
            [read_token_shard(str(out / s)) for s in shards])
        # Byte tokenizer: every id < 256; separators (id 0) appear once
        # per document.
        assert int(tokens.max()) < 256
        assert int((tokens == 0).sum()) == 3

    def test_shards_feed_the_dataset(self, tmp_path):
        paths = _corpus(tmp_path, n_files=2)
        out = tmp_path / 'shards'
        tokenize_tool.main(['--input'] + paths + ['--out', str(out)])
        ds = TokenDataset(str(out), batch_size=4, seq_len=64,
                          host_rank=0, num_hosts=1, seed=0)
        batch = ds.next_batch()
        assert batch['inputs'].shape == (4, 64)
        assert batch['targets'].shape == (4, 64)
        ds.close()

    def test_jsonl_field(self, tmp_path):
        p = tmp_path / 'rows.jsonl'
        p.write_text('\n'.join(
            '{"text": "row %d content here"}' % i for i in range(5)))
        out = tmp_path / 'shards'
        rc = tokenize_tool.main(['--input', str(p), '--out', str(out),
                                 '--jsonl-field', 'text'])
        assert rc == 0
        tokens = read_token_shard(str(out / 'shard_00000.bin'))
        assert int((tokens == 0).sum()) == 5  # one sep per row

    def test_val_split(self, tmp_path):
        paths = _corpus(tmp_path, n_files=4, chars=8000)
        out = tmp_path / 'shards'
        tokenize_tool.main(['--input'] + paths +
                           ['--out', str(out), '--shard-tokens', '2048',
                            '--val-fraction', '0.25'])
        train_shards = [p for p in os.listdir(out) if p.endswith('.bin')]
        val_shards = os.listdir(out / 'val')
        assert train_shards and val_shards
        # Roughly a quarter go to val.
        frac = len(val_shards) / (len(val_shards) + len(train_shards))
        assert 0.1 <= frac <= 0.4, (len(val_shards), len(train_shards))

    def test_tokenize_then_train_with_validation(self, tmp_path):
        """The full data loop: tokenize with a val split, train on the
        shards, and the validation pass reports a loss."""
        paths = _corpus(tmp_path, n_files=4, chars=20000)
        out = tmp_path / 'shards'
        tokenize_tool.main(['--input'] + paths +
                           ['--out', str(out), '--shard-tokens', '8192',
                            '--val-fraction', '0.34'])
        assert os.path.isdir(out / 'val') and os.listdir(out / 'val')
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        proc = subprocess.run(
            [sys.executable, '-m', 'skypilot_tpu.train.run',
             '--model', 'test-tiny', '--batch', '8', '--seq', '32',
             '--steps', '2', '--log-every', '1',
             '--data-dir', str(out), '--val-dir', str(out / 'val'),
             '--eval-every', '2', '--eval-batches', '2'],
            capture_output=True, text=True, timeout=420, env=env,
            check=False)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert 'val_loss=' in proc.stderr

    def test_cli_module_invocation(self, tmp_path):
        p = tmp_path / 'd.txt'
        p.write_text('hello world ' * 100)
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        proc = subprocess.run(
            [sys.executable, '-m', 'skypilot_tpu.train.tokenize_tool',
             '--input', str(p), '--out', str(tmp_path / 'o')],
            capture_output=True, text=True, timeout=120, env=env,
            check=False)
        assert proc.returncode == 0, proc.stderr[-1000:]
        assert 'shards' in proc.stdout
