"""Family `smallthinker`: what `kinds/train_family_blocks.py` needs to
train a configuration of this architecture and to decide `correct`:
the program's model at the configuration file's sizes, its initialiser
and loss, the plain reference (`reference/smallthinker_reference.py`),
the forward comparison, and the step's counters as facts."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import smallthinker as st

from core import draws, sparse_counts
from reference import smallthinker_reference as reference


def model_of(config):
    """The program's config: the published keys, the chip's share, the
    first `num_hidden_layers` entries of the two layouts."""
    layers = config["num_hidden_layers"]
    train = config["train"]
    published = config.get("published", {})
    return st.SmallThinkerConfig(
        vocab_size=published.get("vocab_size", config["vocab_size"]),
        hidden_size=config["hidden_size"], num_layers=layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_ffn_hidden_size=config["moe_ffn_hidden_size"],
        num_experts=config["moe_router_outputs"],
        experts_per_token=config["moe_num_active_primary_experts"],
        rope_layout=tuple(config["rope_layout"][:layers]),
        sliding_window_layout=tuple(config["sliding_window_layout"][:layers]),
        sliding_window_size=config["sliding_window_size"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        experts_held=tuple(config["experts_held"]),
        vocab_held=(config["vocab_held"][0], train["vocab_rows"]))


def id_vocab(config):
    """Ids are drawn from the rows held, not from the padding."""
    return config["vocab_held"][1]


init_params = st.init_smallthinker_params
loss_fn = st.smallthinker_loss_fn


def reference_config(model):
    return dict(num_layers=model.num_layers, num_heads=model.num_heads,
                num_kv_heads=model.num_kv_heads, head_dim=model.head_dim,
                experts_per_token=model.experts_per_token,
                num_experts=model.num_experts, experts_held=model.held,
                rope_layout=model.rope_layout,
                sliding_window_layout=model.sliding_window_layout,
                sliding_window_size=model.sliding_window_size,
                rope_theta=model.rope_theta, rms_norm_eps=model.rms_norm_eps)


def windows(model):
    return [model.sliding_window_size if w else None
            for w in model.sliding_window_layout]


def describe(model, seq, micro):
    """`facts["model"]`: the sizes the counting functions need."""
    layer, expert, tables = st.smallthinker_param_count(model)
    # the embedding is looked up, not multiplied: half the two tables
    dense = model.num_layers * layer + tables // 2
    return {"layers": model.num_layers, "hidden": model.hidden_size,
            "heads": model.num_heads, "kv_heads": model.num_kv_heads,
            "head_dim": model.head_dim, "ffn": model.moe_ffn_hidden_size,
            "experts_held": model.held[1], "seq": seq,
            "micro_batch_per_chip": micro,
            "pairs_by_layer": [sparse_counts.mask_pairs(seq, w)
                               for w in windows(model)],
            "dense_params_used": dense, "expert_params": expert,
            "n_params": (model.num_layers * (layer + model.held[1] * expert)
                         + tables)}


def check_positions(seed, seq, n, window):
    """`n` seeded positions of a row, half of them past the window (where
    a window layer and a global layer see different keys)."""
    rs = np.random.RandomState(draws.seed32(seed, 41))
    far = min(window, seq // 2)
    near = rs.choice(far, n - n // 2, replace=False)
    past = far + rs.choice(seq - far, n // 2, replace=False)
    return np.sort(np.concatenate([near, past])).astype(np.int32)


def reference_readings(ctx, params, ids, model, tr, round_to=None,
                       fault=None, choice=None):
    """The plain float32 reference on the first batch, one row at a time
    (what a chip holds beside the engine), ONE program: the mean loss
    over the rows, and of the first row the logits and the router's
    values at the seeded positions and the experts used at every
    position. `choice` (layers, S, k) is for the first row alone."""
    cfg = reference_config(model)
    seq = ids.shape[1] - 1
    positions = check_positions(ctx.seed, seq, tr["check_positions"],
                                model.sliding_window_size)
    # (the seeded positions are an argument, not a constant: the same
    # program, and the same compile cache entry, under every seed)
    fn = jax.jit(lambda p, row, at, choice: reference.loss_logits_routers(
        p, row, at, cfg, q_block=min(1024, seq), chunk=min(2048, seq),
        round_to=round_to, choice=choice, fault=fault))
    if choice is not None:
        choice, ids = jnp.asarray(choice)[:, None], ids[:1]
    rows = [fn(params, jnp.asarray(ids[i:i + 1]), jnp.asarray(positions),
               choice) for i in range(ids.shape[0])]
    return {"loss": float(np.mean([float(r[0]) for r in rows])),
            "positions": positions,
            "logits": np.asarray(rows[0][1][0], np.float32),
            "routers": np.asarray(rows[0][2][:, 0], np.float32),
            "choice": np.asarray(rows[0][3][:, 0])}


def program_forward(params, ids, model, positions):
    """The program's forward on the first row: (float32 logits at
    `positions` (n, rows), the experts it chose (layers, S, k))."""
    got, facts = jax.jit(lambda p, r, at: st.smallthinker_logits(
        p, model, r, at))(params, jnp.asarray(ids[:1, :-1]),
                          jnp.asarray(positions))
    return np.asarray(got[0], np.float32), np.asarray(facts["moe_choice"])


def judge_forward(ctx, params, ids, model, tr, ref, got, choice):
    """Logits `got` (n, rows) at the seeded positions of the first row,
    made with the experts `choice` (layers, S, k), against the
    reference's. Returns (why_not, facts).

    With random weights the sixth and seventh router values of a token
    lie about 0.09 apart in the mean and bf16 activations move each by a
    few thousandths, so at a few positions in a hundred a program picks
    another sixth expert than the reference, and what follows there
    follows from another input. So the reference is run once more on
    the row, MADE TO USE the choices handed in at every position (its p
    is the softmax of its own router values at them), and judges them:
    at each seeded position and layer, every expert the handed choice
    and its own six largest disagree on has to lie within
    `route_epsilon` of its own boundary between chosen and not (a near
    tie); anything else is a fault of the router. Then NO position is
    left out: at every one the largest logit difference over the held
    rows, in units of the reference logits' standard deviation there,
    stays under `logit_tolerance`."""
    positions = ref["positions"]
    t0 = time.perf_counter()
    forced = reference_readings(ctx, params, ids, model, tr, choice=choice)
    want, routers = forced["logits"], forced["routers"]      # (L, n, E)
    chosen = choice[:, positions]                            # (L, n, k)
    k = model.experts_per_token
    ranked = np.sort(routers, axis=-1)[..., ::-1]
    boundary = (ranked[..., k - 1] + ranked[..., k]) / 2     # (L, n)
    own = np.argsort(-routers, axis=-1)[..., :k]
    why_not, ties = [], []
    differ_at = np.zeros(len(positions), bool)
    for i in range(len(positions)):
        for l in range(chosen.shape[0]):
            differ = set(chosen[l, i].tolist()) ^ set(own[l, i].tolist())
            if not differ:
                continue
            differ_at[i] = True
            off = float(max(abs(routers[l, i, e] - boundary[l, i])
                            for e in differ))
            ties.append(off)
            if off > tr["route_epsilon"]:
                why_not.append(
                    f"layer {l} position {positions[i]}: experts "
                    f"{sorted(chosen[l, i].tolist())} were chosen, the "
                    f"reference's own are {sorted(own[l, i].tolist())}, "
                    f"{off:.4f} from a tie (allowed {tr['route_epsilon']})")
    err = np.abs(got - want).max(-1) / want.std(-1)
    worst = float(err.max())
    ctx.log(f"forward at {len(positions)} positions of the first row "
            f"against the reference made to use the same experts "
            f"({time.perf_counter() - t0:.1f} s): logit error over the "
            f"reference's std max {worst:.5f} median "
            f"{float(np.median(err)):.5f}; {int(differ_at.sum())} "
            f"positions where a choice differs from the reference's own, "
            f"none left out; distances from a tie there "
            f"{[round(t, 5) for t in sorted(ties)]}")
    if not worst <= tr["logit_tolerance"]:
        why_not.append(f"forward logits differ from the reference's by "
                       f"{worst} of their std, more than "
                       f"{tr['logit_tolerance']}")
    return why_not[:8], {"logit_error_max": worst,
                         "route_differ_share": float(differ_at.mean()),
                         "route_farthest_from_tie": max(ties, default=0.0)}


def compare_forward(ctx, params, ids, model, tr, ref):
    """The program's forward on the first row through `judge_forward`."""
    got, choice = program_forward(params, ids, model, ref["positions"])
    return judge_forward(ctx, params, ids, model, tr, ref, got, choice)


def _expert_case(params, ids, model, seed):
    """The expert layer alone at the timed sizes: layer 0's held experts
    on the first batch's normed embeddings x (T, H), routed by layer 0's
    router (idx, p), and a seeded c for `sum(y * c)`."""
    from deepspeed_tpu.ops import moe
    lp = params["h_0"]
    x = reference._rms(params["tok_emb"][jnp.asarray(ids[:, :-1])],
                       lp["ln_1"]["w"], model.rms_norm_eps)
    x = x.reshape(-1, x.shape[-1])
    idx, p, _ = jax.jit(lambda x, w: moe.route_top_k(
        x, w, model.experts_per_token))(x, lp["router"])
    c = jax.random.normal(
        jax.random.PRNGKey(draws.seed32(seed, 43) % (2 ** 31)), x.shape)
    return x, p, lp["experts"], idx, c


def _named(grads):
    x, p, tables = grads
    return {"x": np.asarray(x, np.float32), "p": np.asarray(p, np.float32),
            **{n: np.asarray(t, np.float32) for n, t in tables.items()}}


def program_expert_gradients(params, ids, model, seed):
    """The program's hand-written backward pass of `sum(y * c)` (the
    grouped products' two backward products at the real tiles, the
    buffer of the timed step): gradients over x, p and the three tables."""
    from deepspeed_tpu.ops import moe

    def loss(x, p, experts, idx, c):
        low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                     experts)
        y, _ = moe.dropless_reglu_experts(
            x.astype(jnp.bfloat16), idx, p, low, model.held,
            model.num_experts)
        return jnp.sum(y * c)

    return _named(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
        *_expert_case(params, ids, model, seed)))


def reference_expert_gradients(params, ids, model, seed, round_to=None):
    """`jax.grad` of the reference's plain expert sum, a row of the batch
    at a time (what a chip holds beside the engine)."""
    x, p, experts, idx, c = _expert_case(params, ids, model, seed)
    first = model.held[0]
    grad = jax.jit(jax.grad(
        lambda x, p, experts, idx, c: jnp.sum(reference._experts(
            x, None, p, idx, experts, first, round_to) * c),
        argnums=(0, 1, 2)))
    seq = ids.shape[1] - 1
    row = lambda a, i: a[i * seq:(i + 1) * seq]
    parts = [grad(row(x, i), row(p, i), experts, row(idx, i), row(c, i))
             for i in range(ids.shape[0])]
    return _named((jnp.concatenate([g[0] for g in parts]),
                   jnp.concatenate([g[1] for g in parts]),
                   jax.tree_util.tree_map(lambda *t: sum(t),
                                          *[g[2] for g in parts])))


def gradient_errors(got, want):
    """Norm of the difference over the norm of the reference's, by name."""
    return {n: float(np.linalg.norm(got[n] - want[n])
                     / np.linalg.norm(want[n])) for n in want}


def compare_backward(ctx, params, ids, model, tr):
    """The expert layer's own backward pass against `jax.grad` of the
    reference's expert sum: each of the five gradients within
    `expert_grad_tolerance` of the reference's, by norm. Returns
    (why_not, facts)."""
    t0 = time.perf_counter()
    errors = gradient_errors(
        program_expert_gradients(params, ids, model, ctx.seed),
        reference_expert_gradients(params, ids, model, ctx.seed))
    ctx.log(f"expert layer's backward pass against jax.grad of the "
            f"reference's ({time.perf_counter() - t0:.1f} s): norm of the "
            f"difference over the reference's norm "
            f"{ {n: round(e, 5) for n, e in errors.items()} }")
    worst = max(errors.values())
    why_not = [] if worst <= tr["expert_grad_tolerance"] else [
        f"the expert layer's gradients differ from the reference's by "
        f"{errors} of their norm, more than {tr['expert_grad_tolerance']}"]
    return why_not, {"expert_grad_error_max": worst}


def controls():
    """(what, the reference's keywords, has it to come out NOT correct)
    for `tools/train_controls.py`: the reference at the program's own
    precision (inside every limit), at the nearest precision below it,
    and with each planted fault (outside one at least)."""
    return ([("operands rounded to bfloat16",
              {"round_to": jnp.bfloat16}, False),
             ("operands rounded to float8_e4m3fn",
              {"round_to": jnp.float8_e4m3fn}, True)]
            + [("fault " + f, {"fault": f}, True) for f in reference.FAULTS])


def judge_control(ctx, params, ids, model, tr, ref, want_grads, **kw):
    """A changed reference in the program's place, through the cell's
    own comparison: its loss against `loss_tolerance`, its logits and
    choices through `judge_forward`, and (for a precision) its expert
    gradients against `expert_grad_tolerance`. Returns why_not."""
    got = reference_readings(ctx, params, ids, model, tr, **kw)
    why_not, _ = judge_forward(ctx, params, ids, model, tr, ref,
                               got["logits"], got["choice"])
    off = abs(got["loss"] - ref["loss"])
    ctx.log(f"loss {got['loss']:.6f} against {ref['loss']:.6f}: off by "
            f"{off:.6f} (limit {tr['loss_tolerance']})")
    if not off <= tr["loss_tolerance"]:
        why_not.append(f"loss off by {off}")
    if "round_to" in kw:
        errors = gradient_errors(reference_expert_gradients(
            params, ids, model, ctx.seed, kw["round_to"]), want_grads)
        ctx.log(f"expert gradients off by "
                f"{ {n: round(e, 5) for n, e in errors.items()} } of their "
                f"norm (limit {tr['expert_grad_tolerance']})")
        if not max(errors.values()) <= tr["expert_grad_tolerance"]:
            why_not.append(f"expert gradients off by {errors}")
    return why_not


def step_counters(aux):
    """What one step's `engine.last_aux` holds, on the host: (layers,
    held) assignments landed (summed over the micro batches)."""
    return sum(np.asarray(a["moe_counts"], np.int64) for a in aux)


def held_share(per_step, model, tokens_per_step):
    """Landed over offered assignments of some steps."""
    offered = (len(per_step) * tokens_per_step * model.experts_per_token
               * model.num_layers)
    return float(np.sum(per_step)) / offered


def counter_facts(per_step, model, tokens_per_step, traced):
    """`facts["experts"]` from the per-step counters of the window:
    landed and offered assignments, the fullest expert over the mean,
    and the landed count a step of the traced steps."""
    counts = np.stack(per_step)                    # (steps, layers, held)
    offered = (len(per_step) * tokens_per_step * model.experts_per_token
               * model.num_layers)
    by_expert = counts.sum(0).astype(np.float64)   # (layers, held)
    per_layer = tokens_per_step * model.experts_per_token
    traced_counts = counts[traced[0]:traced[1]] if traced else counts[:0]
    return {"landed": int(counts.sum()), "offered": int(offered),
            "load_max_over_mean_pct": float(
                100.0 * (by_expert.max(-1) / by_expert.mean(-1)).max()),
            # the fullest layer of any step, and every layer's share
            # of its assignments over the window
            "layer_share_max_pct": float(
                100.0 * counts.sum(2).max() / per_layer),
            "layer_share_by_layer_pct": [
                round(100.0 * float(v) / (len(per_step) * per_layer), 2)
                for v in counts.sum((0, 2))],
            "landed_per_step_min": int(counts.sum((1, 2)).min()),
            "landed_per_step_max": int(counts.sum((1, 2)).max()),
            "landed_per_traced_step": float(
                traced_counts.sum((1, 2)).mean()) if len(traced_counts)
            else None}
