"""ISSUE 30: what the scanned decoder layer's checkpoint keeps.

``LlamaConfig(scan_layers=True, recompute=True)`` is one ``jax.checkpoint``
per layer with a ``save_only_these_names`` policy: the attention side's
hidden-width tensors, flash's two residuals and the two wide MLP
projections are kept by name (``models/llama.py::SCAN_SAVED_NAMES``), so
the backward repeats no matmul and no ``flash_fwd_lse``. Tier-1 (the
four-step loss parity of scan against loop is ``tests/test_gpt.py``'s, in
the slow tier).
"""

import numpy as np
import pytest

import paddle_tpu as paddle

# the stacked parameters of a scanned Llama, as ``_scan_names`` sorts them
_SCAN_PARAMS = ("input_layernorm.weight", "mlp.down_proj.weight",
                "mlp.gate_proj.weight", "mlp.up_proj.weight",
                "post_attention_layernorm.weight",
                "self_attn.k_proj.weight", "self_attn.o_proj.weight",
                "self_attn.q_proj.weight", "self_attn.v_proj.weight")
_B, _E, _H, _HKV, _I, _LAYERS = 1, 64, 4, 2, 128, 3


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _walk(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _walk(sub)


class TestScanRecomputePolicy:
    """ISSUE 30: the scanned layer's checkpoint keeps ``SCAN_SAVED_NAMES``
    and the carry, so no matmul and no ``flash_fwd_lse`` runs a second
    time in the backward. ``path`` is how attention ran: ``xla`` is the
    CPU's fused softmax attention, ``flash`` the Pallas kernels in
    interpret mode at a length that takes them (what the chip runs)."""

    @pytest.fixture(scope="class", params=["xla", "flash"])
    def scanned(self, request, scanned_layers_fn):
        """``(path, model, f, args)``: ``f(*args)`` is the scan over the
        layers, ``args`` the hidden states and the stacked parameters."""
        import jax.numpy as jnp

        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.ops import nn_ops

        path = request.param
        length = 128 if path == "flash" else 32
        cfg = LlamaConfig.tiny(vocab=128, hidden=_E, layers=_LAYERS,
                               heads=_H, kv_heads=_HKV, inter=_I,
                               max_pos=128)
        cfg.scan_layers = cfg.recompute = True
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).model
        assert tuple(model._scan_names) == _SCAN_PARAMS
        f, stacked = scanned_layers_fn(model)
        h = jnp.asarray(np.random.default_rng(1).standard_normal(
            (_B, length, _E)), jnp.float32)
        with pytest.MonkeyPatch.context() as mp:
            if path == "flash":
                mp.setattr(nn_ops, "_sdpa_flash_backend_ok", lambda: True)
            yield path, model, f, (h, *stacked)

    @staticmethod
    def _kept_shapes(path, length):
        d = _E // _H
        shapes = [(_B, length, _H, d), (_B, length, _HKV, d),
                  (_B, length, _HKV, d), (_B, length, _E),
                  (_B, length, _I), (_B, length, _I)]
        if path == "flash":
            shapes += [(_B, _H, length, d), (_B, _H, length)]
        return sorted(shapes)

    @pytest.fixture(scope="class")
    def grads(self, scanned):
        """Gradients of every stacked parameter under the committed policy
        and with no checkpoint at all."""
        import jax

        _, model, f, args = scanned
        out = {}
        for recompute in (True, False):
            model.config.recompute = recompute
            g = jax.grad(lambda *a: (f(*a) ** 2).sum(),
                         argnums=tuple(range(1, len(args))))
            try:
                out[recompute] = [np.asarray(x) for x in g(*args)]
            finally:
                model.config.recompute = True
        return out

    @pytest.mark.parametrize("name", _SCAN_PARAMS)
    def test_gradient_equals_the_one_without_recompute(self, grads, name):
        i = _SCAN_PARAMS.index(name)
        kept, plain = grads[True][i], grads[False][i]
        assert np.abs(plain).max() > 0
        np.testing.assert_allclose(kept, plain, rtol=2e-5,
                                   atol=2e-6 * np.abs(plain).max())

    def test_residuals_are_the_named_values_and_the_carry(self, scanned,
                                                          capsys):
        import jax

        path, model, _, args = scanned
        h, layer = args[0], [a[0] for a in args[1:]]
        body = model._scan_body(model.rope_cos._data, model.rope_sin._data,
                                h)
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(body, h, layer)
        lines = capsys.readouterr().out.strip().splitlines()
        assert any(l.endswith("from the argument carry") for l in lines)
        made = [l for l in lines if "from the argument" not in l
                and "from a constant" not in l]
        shapes = sorted(tuple(int(n) for n in l.split("]")[0].split("[")[1]
                              .split(",")) for l in made)
        assert shapes == self._kept_shapes(path, h.shape[1]), lines

    def test_backward_repeats_no_matmul(self, scanned):
        """Seven projections and their fourteen backward matmuls a layer,
        as with no checkpoint at all; the XLA attention's two einsums are
        not named, so that path (not the chip's) still repeats them."""
        import jax

        path, model, f, args = scanned

        def dots():                     # a new function: a new trace
            g = jax.grad(lambda *a: f(*a).sum(),
                         argnums=tuple(range(1, len(args))))
            return sum(e.primitive.name == "dot_general"
                       for e in _walk(jax.make_jaxpr(g)(*args).jaxpr))

        kept = dots()
        model.config.recompute = False
        try:
            plain = dots()
        finally:
            model.config.recompute = True
        assert kept == plain + (2 if path == "xla" else 0), (kept, plain)

    @pytest.mark.parametrize("scanned", ["flash"], indirect=True)
    def test_flash_forward_kernel_runs_once_a_layer(
            self, scanned, flash_kernels_not_interpreted):
        """The program lowered for the chip holds ``flash_fwd_lse`` once
        (the forward scan's), beside the two backward kernels."""
        import re

        import jax

        _, _, f, args = scanned
        g = jax.grad(lambda *a: f(*a).sum(),
                     argnums=tuple(range(1, len(args))))
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
        text = jax.jit(g).trace(*specs).lower(
            lowering_platforms=("tpu",)).as_text()
        assert sorted(re.findall(r'kernel_name = "([^"]+)"', text)) == \
            ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd_lse"]

    def test_gauges_say_what_a_layer_keeps(self, scanned):
        import jax

        from paddle_tpu import observability as obs

        path, _, f, args = scanned
        length = args[0].shape[1]
        kept = self._kept_shapes(path, length) + [(_B, length, _E)]
        obs.enable()
        try:
            obs.reset()
            jax.make_jaxpr(jax.grad(lambda *a: f(*a).sum()))(*args)
            snap = obs.snapshot()
        finally:
            obs.disable()
        assert snap["train.scan.saved_values"] == len(kept) - 1
        assert snap["train.scan.saved_bytes_per_layer"] == \
            4 * sum(int(np.prod(s)) for s in kept)

    def test_names_pass_eager_values_through(self):
        from paddle_tpu.models.llama import _keep

        x = paddle.to_tensor(np.ones((2, 3), np.float32))
        assert _keep(x, "attn_q") is x


@pytest.mark.parametrize("variant", ["plain", "segments", "dropout"])
def test_names_leave_other_flash_programs_as_they_were(
        variant, monkeypatch, flash_kernels_not_interpreted):
    """Every differentiated flash call runs the tagged forward rules (GPT,
    ERNIE, BERT, the hybrid cell). Outside a checkpoint policy the program
    lowered for the chip is, byte for byte, the one without the names."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import flash_attention as fa

    qkv = jax.ShapeDtypeStruct((1, 4, 256, 128), jnp.bfloat16)
    segs = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32)
    core, extra = {
        "plain": (lambda q, k, v: fa._flash_core(q, k, v, True, 0.1), ()),
        "segments": (lambda q, k, v, a, b: fa._flash_core_seg(
            q, k, v, a, b, True, 0.1), (segs, segs)),
        "dropout": (lambda q, k, v, a, b, s: fa._flash_core_drop(
            q, k, v, a, b, s, True, 0.1, 0.1), (segs, segs, seed)),
    }[variant]

    def lowered():                           # a new function: a new trace
        g = jax.grad(lambda *a: core(*a).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))
        return jax.jit(g).trace(qkv, qkv, qkv, *extra).lower(
            lowering_platforms=("tpu",)).as_text()

    texts = []
    for name in (fa.checkpoint_name, lambda x, name: x):
        monkeypatch.setattr(fa, "checkpoint_name", name)
        texts.append(lowered())     # one call site: a kernel's bytes hold it
    assert "flash_fwd_lse" in texts[0] and "flash_bwd_dkv" in texts[0]
    assert texts[0] == texts[1]
