"""One function per paper figure/table (Figs. 2-14).

Each returns CSV rows "name,us_per_call,derived" where derived is the mean
performance ratio across the instance suite (the paper's y-axis).
"""
from __future__ import annotations

from typing import List

from .common import alg, box_row, evaluate, repeat_seeds

SIGMAS = [0.0, 0.5, 1.0, 2.0, 4.0]


def fig2_bestfit_norms() -> List[str]:
    out = []
    for norm in ["l1", "l2", "linf"]:
        r, s = evaluate(alg("best_fit", norm=norm))
        out.append(box_row(f"fig2/best_fit_{norm}", r, s))
    return out


def fig3_nonclairvoyant() -> List[str]:
    out = []
    for name in ["first_fit", "mru", "next_fit", "rr_next_fit"]:
        r, s = evaluate(alg(name))
        out.append(box_row(f"fig3/{name}", r, s))
    r, s = evaluate(alg("best_fit", norm="linf"))
    out.append(box_row("fig3/best_fit_linf", r, s))
    return out


def fig4_cbdt_rho() -> List[str]:
    out = []
    for rho_days in [0.05, 0.1, 0.25, 0.5, 1.0, 2.0]:
        r, s = evaluate(alg("cbdt", rho=rho_days * 86400.0))
        out.append(box_row(f"fig4/cbdt_rho{rho_days}d", r, s))
    return out


def fig5_nrt() -> List[str]:
    out = []
    for name in ["nrt_standard", "nrt_prioritized"]:
        r, s = evaluate(alg(name))
        out.append(box_row(f"fig5/{name}", r, s))
    return out


def fig6_cbd_beta() -> List[str]:
    out = []
    for beta in [1.5, 2.0, 4.0, 8.0, 16.0]:
        r, s = evaluate(alg("cbd", beta=beta))
        out.append(box_row(f"fig6/cbd_beta{beta:g}", r, s))
    return out


def fig7_hybrid() -> List[str]:
    out = []
    for name in ["hybrid", "reduced_hybrid", "hybrid_direct_sum",
                 "reduced_hybrid_direct_sum"]:
        r, s = evaluate(alg(name))
        out.append(box_row(f"fig7/{name}", r, s))
    return out


def fig8_clairvoyant() -> List[str]:
    out = []
    cases = [("cbdt_rho0.25d", alg("cbdt", rho=0.25 * 86400)),
             ("nrt_prioritized", alg("nrt_prioritized")),
             ("greedy", alg("greedy")),
             ("cbd_beta2", alg("cbd", beta=2.0)),
             ("reduced_hybrid", alg("reduced_hybrid")),
             ("first_fit", alg("first_fit"))]
    for name, f in cases:
        r, s = evaluate(f)
        out.append(box_row(f"fig8/{name}", r, s))
    return out


def fig9_classify_error() -> List[str]:
    out = []
    for sigma in SIGMAS:
        for name, f in [("cbdt_rho0.25d", alg("cbdt", rho=0.25 * 86400)),
                        ("cbd_beta2", alg("cbd", beta=2.0))]:
            r, s = evaluate(f, sigma=sigma, seeds=repeat_seeds())
            out.append(box_row(f"fig9/{name}/sigma{sigma:g}", r, s))
    r, s = evaluate(alg("first_fit"))
    out.append(box_row("fig9/first_fit/flat", r, s))
    return out


def fig10_rcp_ppe() -> List[str]:
    out = []
    for sigma in SIGMAS:
        for name in ["rcp", "ppe", "rcp_modified", "ppe_modified"]:
            r, s = evaluate(alg(name), sigma=sigma, seeds=repeat_seeds())
            out.append(box_row(f"fig10/{name}/sigma{sigma:g}", r, s))
    return out


def fig11_lifetime_alignment() -> List[str]:
    out = []
    for sigma in SIGMAS:
        cases = [("la_binary", alg("lifetime_alignment", mode="binary")),
                 ("la_geometric", alg("lifetime_alignment", mode="geometric")),
                 ("cbd_beta2", alg("cbd", beta=2.0)),
                 ("reduced_hybrid", alg("reduced_hybrid"))]
        for name, f in cases:
            r, s = evaluate(f, sigma=sigma, seeds=repeat_seeds())
            out.append(box_row(f"fig11/{name}/sigma{sigma:g}", r, s))
    return out


def fig12_overall() -> List[str]:
    out = []
    for sigma in SIGMAS:
        cases = [("nrt_prioritized", alg("nrt_prioritized")),
                 ("greedy", alg("greedy")),
                 ("ppe_modified", alg("ppe_modified")),
                 ("la_binary", alg("lifetime_alignment", mode="binary"))]
        for name, f in cases:
            r, s = evaluate(f, sigma=sigma, seeds=repeat_seeds())
            out.append(box_row(f"fig12/{name}/sigma{sigma:g}", r, s))
    r, s = evaluate(alg("first_fit"))
    out.append(box_row("fig12/first_fit/flat", r, s))
    return out


def fig13_huawei() -> List[str]:
    out = []
    cases = [("first_fit", alg("first_fit")),
             ("best_fit_l2", alg("best_fit", norm="l2")),
             ("rr_next_fit", alg("rr_next_fit")),
             ("nrt_prioritized", alg("nrt_prioritized")),
             ("greedy", alg("greedy")),
             ("reduced_hybrid", alg("reduced_hybrid"))]
    for name, f in cases:
        r, s = evaluate(f, suite="huawei")
        out.append(box_row(f"fig13/{name}", r, s))
    return out


def fig14_uniform_errors() -> List[str]:
    out = []
    for eps in [1.0, 4.0, 16.0, 100.0, 10000.0]:
        for name, f in [("nrt_prioritized", alg("nrt_prioritized")),
                        ("greedy", alg("greedy")),
                        ("ppe_modified", alg("ppe_modified")),
                        ("la_binary", alg("lifetime_alignment",
                                          mode="binary"))]:
            r, s = evaluate(f, eps=eps, seeds=repeat_seeds())
            out.append(box_row(f"fig14/{name}/eps{eps:g}", r, s))
    return out


def fig15_adaptive() -> List[str]:
    """BEYOND-PAPER: the paper's future-work item (1) - adaptive switching
    between NRT/Greedy/FF on the observed error signal."""
    out = []
    for sigma in SIGMAS:
        for name, f in [("adaptive", alg("adaptive")),
                        ("nrt_prioritized", alg("nrt_prioritized")),
                        ("greedy", alg("greedy"))]:
            r, s = evaluate(f, sigma=sigma, seeds=repeat_seeds())
            out.append(box_row(f"fig15/{name}/sigma{sigma:g}", r, s))
    r, s = evaluate(alg("first_fit"))
    out.append(box_row("fig15/first_fit/flat", r, s))
    return out


def fig16_consolidation() -> List[str]:
    """BEYOND-PAPER: threshold-triggered consolidation as a scenario axis.

    Every scan policy replays the suite twice through the api facade -
    the paper's placement-only setting and its consolidating twin
    (underload drain, threshold 0.25, 32-event planning cadence) - so the
    figure shows which families leave drainable bins behind and how much
    usage-time the bounded-recourse repack buys back.  Rows come in
    ``fig16/<policy>/base`` / ``fig16/<policy>/cons`` pairs (same mean
    performance-ratio metric as every other figure)."""
    import time

    from repro.api import Experiment, SCAN_POLICIES, Setting, instances
    from .common import azure_suite
    base = Setting.clairvoyant()
    cons = base.with_consolidation("underload:t0.25:e32")
    exp = Experiment(instances(list(azure_suite()), name="fig16"),
                     policies=SCAN_POLICIES, settings=(base, cons))
    t0 = time.time()
    res = exp.run()
    secs = (time.time() - t0) / max(len(res.rows()), 1)
    out = []
    for policy in SCAN_POLICIES:
        for setting, tag in ((base, "base"), (cons, "cons")):
            ratios = res.ratios(policy=policy, setting=setting.label())
            out.append(box_row(f"fig16/{policy}/{tag}", ratios, secs))
    return out


ALL_FIGURES = [fig2_bestfit_norms, fig3_nonclairvoyant, fig4_cbdt_rho,
               fig5_nrt, fig6_cbd_beta, fig7_hybrid, fig8_clairvoyant,
               fig9_classify_error, fig10_rcp_ppe, fig11_lifetime_alignment,
               fig12_overall, fig13_huawei, fig14_uniform_errors,
               fig15_adaptive, fig16_consolidation]
